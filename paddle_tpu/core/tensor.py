"""Eager Tensor.

Analog of the reference's eager Tensor (paddle/fluid/pybind/eager.cc:1477 binding over
phi::DenseTensor, autograd meta fluid/eager/autograd_meta.h:61) — redesigned for a
functional runtime: `_data` holds an immutable jax.Array (or a JAX tracer during
program capture), so the SAME eager code runs op-by-op on PJRT *and* under jit trace.
Because jax arrays are immutable, saved-tensor/inplace-version tracking from the
reference (fluid/eager/tensor_wrapper.h) is unnecessary: vjp residuals capture values,
not buffers.

Autograd state mirrors AutogradMeta: `stop_gradient` (default True, like Paddle),
`grad`, and a producer `_grad_node` + `_out_slot` linking into the tape
(see paddle_tpu/autograd/node.py).
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from .device import Place, current_device


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


class Tensor:
    __slots__ = (
        "_buf", "stop_gradient", "_grad_buf", "_grad_node", "_out_slot",
        "name", "persistable", "_retain_grad", "_hooks", "_replay_node",
        "__weakref__",
    )

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None,
                 persistable: bool = False):
        self._buf = data
        self.stop_gradient = stop_gradient
        self._grad_buf: Optional[Tensor] = None
        self._grad_node = None
        self._out_slot = 0
        self._replay_node = None   # (node, slot) set under static recording
        self.name = name
        self.persistable = persistable
        self._retain_grad = False
        self._hooks: Optional[list] = None
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None:
            tc.on_create(self)

    # -- data access: reads/writes route through properties so program capture
    # (paddle_tpu.jit) can lift state (params, opt moments, RNG keys) to program
    # inputs and collect mutations as outputs without touching the real buffers.
    @property
    def _data(self):
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None:
            return tc.on_read(self)
        return self._buf

    @_data.setter
    def _data(self, value):
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None:
            tc.on_write(self, value)
            return
        self._buf = value

    @property
    def grad(self):
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None:
            return tc.on_grad_read(self)
        return self._grad_buf

    @grad.setter
    def grad(self, value):
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None:
            tc.on_grad_write(self, value)
            return
        self._grad_buf = value

    # ---- metadata ------------------------------------------------------------
    @property
    def shape(self) -> list:
        return list(self._buf.shape)

    @property
    def ndim(self) -> int:
        return self._buf.ndim

    @property
    def dtype(self):
        return np.dtype(self._buf.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self._buf.shape)) if self._buf.shape else 1

    @property
    def place(self) -> Place:
        if _is_tracer(self._buf):
            return Place(current_device())
        devs = getattr(self._buf, "devices", None)
        if devs is not None:
            return Place(next(iter(self._buf.devices())))
        return Place(current_device())

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def T(self) -> "Tensor":
        return self.transpose(list(range(self.ndim))[::-1])

    def numel(self) -> int:
        return self.size

    def element_size(self) -> int:
        return self.dtype.itemsize

    def dim(self) -> int:
        return self.ndim

    def is_dist(self) -> bool:
        if _is_tracer(self._buf):
            return False
        sharding = getattr(self._buf, "sharding", None)
        return sharding is not None and getattr(sharding, "num_devices", 1) > 1

    # ---- host interop --------------------------------------------------------
    def numpy(self) -> np.ndarray:
        """Host read. Under program capture this is a stitched BREAK event
        (jit/to_static.py): the compiled program emits the traced value as an
        extra output, and the per-call echo pass hands the caller the true
        array — the signature stays compiled."""
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None and hasattr(tc, "on_materialize"):
            return tc.on_materialize(self)
        if _is_tracer(self._buf):
            raise RuntimeError(
                "Tensor.numpy() is not available while capturing a static program "
                "(data-dependent host access); this triggers a graph break.")
        return np.asarray(self._buf)

    def item(self, *args):
        return self.numpy().item(*args)

    def tolist(self):
        return self.numpy().tolist()

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._buf.shape[0]

    def _convert_scalar(self, kind, caster):
        """Host scalar conversion. Under program capture this is a GUARD
        point (the SOT guard analog, jit/to_static.py): the spy pass records
        the concrete value; replay emits the traced value as a program output
        and the runtime re-specializes when a step's actual value diverges."""
        from .dispatch import _state
        tc = _state.trace_ctx
        if tc is not None and hasattr(tc, "on_scalar"):
            return tc.on_scalar(self, kind, caster)
        return caster(self._data)

    def __bool__(self) -> bool:
        return self._convert_scalar("bool", lambda a: bool(a))

    def __int__(self) -> int:
        return self._convert_scalar("int", lambda a: int(a))

    def __float__(self) -> float:
        # a float guard would re-specialize on every distinct value, so under
        # capture this is a stitched BREAK (traced value rides out as a
        # program output; the echo pass returns the true per-call float)
        return self._convert_scalar("float", lambda a: float(a))

    def __index__(self) -> int:
        return self._convert_scalar("int", lambda a: int(a))

    def __format__(self, spec):
        if self.ndim == 0:
            from .dispatch import _state
            tc = _state.trace_ctx
            if tc is not None and hasattr(tc, "on_materialize"):
                return format(np.asarray(tc.on_materialize(self)).item(), spec)
            if not _is_tracer(self._buf):
                return format(self.item(), spec)
        return str(self)

    # ---- autograd surface ----------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False):
        from ..autograd.backward import backward as _backward
        _backward([self], [grad_tensor], retain_graph=retain_graph)

    def register_hook(self, hook):
        """Register a gradient hook; returns a removable handle (eager hook analog
        of fluid/eager/hooks.h)."""
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)
        hooks = self._hooks
        class _Handle:
            def remove(self_inner):
                if hook in hooks:
                    hooks.remove(hook)
        return _Handle()

    def retain_grads(self):
        self._retain_grad = True

    def clear_grad(self, set_to_zero: bool = False):
        if set_to_zero and self.grad is not None:
            self.grad = Tensor(jnp.zeros_like(self.grad._buf))
        else:
            self.grad = None

    def clear_gradient(self, set_to_zero: bool = False):
        self.clear_grad(set_to_zero)

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name)
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    # ---- conversion / movement ----------------------------------------------
    def to(self, *args, **kwargs) -> "Tensor":
        device = kwargs.pop("device", None)
        dtype = kwargs.pop("dtype", None)
        blocking = kwargs.pop("blocking", None)  # noqa: F841 — async by default on TPU
        for a in args:
            if isinstance(a, str) and a in ("cpu", "tpu", "gpu") or ":" in str(a):
                device = a
            else:
                dtype = a
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            from .device import _parse
            arr = jax.device_put(out._buf, _parse(device))
            t = Tensor(arr, stop_gradient=out.stop_gradient, name=out.name)
            t._grad_node, t._out_slot = out._grad_node, out._out_slot
            out = t
        return out

    def cpu(self) -> "Tensor":
        return self.to(device="cpu")

    def cuda(self, *a, **k) -> "Tensor":  # paddle compat name; routes to the TPU
        return self.to(device="tpu")

    def pin_memory(self) -> "Tensor":
        return self

    def contiguous(self) -> "Tensor":
        return self

    def is_contiguous(self) -> bool:
        return True

    # astype installed by ops package (differentiable cast); cast = alias.

    # ---- misc ----------------------------------------------------------------
    def get_tensor(self):
        return self

    def value(self):
        return self

    def block_until_ready(self) -> "Tensor":
        if not _is_tracer(self._buf):
            jax.block_until_ready(self._buf)
        return self

    def _copy_from(self, other: "Tensor"):
        self._data = other._buf if isinstance(other, Tensor) else jnp.asarray(other)

    def copy_(self, other, blocking: bool = True) -> "Tensor":
        self._copy_from(other)
        return self

    def __repr__(self):
        if _is_tracer(self._buf):
            return (f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}, "
                    f"traced=True, stop_gradient={self.stop_gradient})")
        data = np.asarray(self._buf)
        return (f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}, "
                f"place={self.place}, stop_gradient={self.stop_gradient},\n"
                f"       {np.array2string(data, prefix='       ')})")

    __str__ = __repr__

    # Elementwise __eq__ is installed by ops.logic; keep identity hashing so
    # Tensors can key dicts (optimizer state, reducers) like Paddle's Tensor.
    __hash__ = object.__hash__

    # numpy interop
    def __array__(self, dtype=None):
        arr = self.numpy()
        return arr.astype(dtype) if dtype is not None else arr


class Parameter(Tensor):
    """Trainable tensor (python/paddle/base/framework.py Parameter analog)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip", "is_distributed")

    def __init__(self, data, name=None, trainable=True):
        super().__init__(data, stop_gradient=not trainable, name=name, persistable=True)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()
