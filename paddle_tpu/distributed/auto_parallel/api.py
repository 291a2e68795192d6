"""Semi-auto parallel API (reference: python/paddle/distributed/auto_parallel/
api.py — shard_tensor:220, reshard:733, shard_layer:844; process_mesh.py:85;
C++ DistTensor phi/core/distributed/auto_parallel/dist_tensor.h:39).

TPU-native: a DistTensor is just a Tensor whose jax.Array carries a
NamedSharding over a jax.sharding.Mesh; reshard is device_put (eager) or
with_sharding_constraint (traced); sharding propagation is XLA GSPMD — the 115
hand-written spmd rules of the reference collapse into the compiler.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core.tensor import Tensor
from ...core.dispatch import unwrap, _state
from .placement import (Placement, Replicate, Shard, Partial, placements_to_spec,
                        spec_to_placements)


class ProcessMesh:
    """reference: distributed/auto_parallel/process_mesh.py:85."""

    def __init__(self, mesh, dim_names=None, shape=None, process_ids=None):
        arr = np.asarray(mesh, dtype=np.int64)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        self._ids = arr
        self._dim_names = list(dim_names)
        self._jax_mesh = None

    @property
    def shape(self):
        return list(self._ids.shape)

    @property
    def ndim(self):
        return self._ids.ndim

    @property
    def dim_names(self):
        return self._dim_names

    @property
    def process_ids(self):
        return self._ids.reshape(-1).tolist()

    def get_dim_size(self, name):
        return self._ids.shape[self._dim_names.index(name)]

    def get_mesh_with_dim(self, name, index=None):
        """Submesh along an axis (reference: process_mesh.py get_mesh_with_dim)."""
        axis = self._dim_names.index(name)
        moved = np.moveaxis(self._ids, axis, 0)
        names = [name] + [n for n in self._dim_names if n != name]
        if index is not None:
            return ProcessMesh(moved[index], names[1:])
        return ProcessMesh(moved, names)

    def get_group(self, dim_name=None):
        from ..collective import new_group
        return new_group(self.process_ids)

    @classmethod
    def from_jax_mesh(cls, jmesh: Mesh) -> "ProcessMesh":
        """Wrap an existing jax.sharding.Mesh, deriving process ids from the
        actual device array (preserves permuted / topology-aware layouts —
        rebuilding from np.arange would silently reorder devices)."""
        ids = np.vectorize(lambda d: d.id, otypes=[np.int64])(jmesh.devices)
        pm = cls(ids, list(jmesh.axis_names))
        pm._jax_mesh = jmesh
        return pm

    def jax_mesh(self) -> Mesh:
        if self._jax_mesh is None:
            devices = np.asarray(jax.devices(), dtype=object)
            dev_arr = np.empty(self._ids.shape, dtype=object)
            flat_ids = self._ids.reshape(-1)
            dev_flat = [devices[i] for i in flat_ids]
            dev_arr = np.asarray(dev_flat, dtype=object).reshape(self._ids.shape)
            self._jax_mesh = Mesh(dev_arr, axis_names=tuple(self._dim_names))
        return self._jax_mesh

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh) and
                np.array_equal(self._ids, other._ids) and
                self._dim_names == other._dim_names)

    def __hash__(self):
        return hash((self._ids.tobytes(), tuple(self._dim_names)))

    def __repr__(self):
        return f"ProcessMesh(shape={self.shape}, dim_names={self._dim_names})"


_global_mesh = None


def set_mesh(mesh: ProcessMesh):
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> ProcessMesh:
    return _global_mesh


def _norm_placements(placements, mesh: ProcessMesh):
    if placements is None:
        return [Replicate() for _ in range(mesh.ndim)]
    out = list(placements)
    while len(out) < mesh.ndim:
        out.append(Replicate())
    return out


def _sharding_for(mesh: ProcessMesh, placements, ndim) -> NamedSharding:
    spec = placements_to_spec(placements, ndim, mesh.dim_names)
    return NamedSharding(mesh.jax_mesh(), spec)


def shard_tensor(data, mesh: ProcessMesh, placements, dtype=None, place=None,
                 stop_gradient=None):
    """reference: auto_parallel/api.py:220 — returns a DistTensor (here: a Tensor
    whose array is device_put with a NamedSharding)."""
    t = data if isinstance(data, Tensor) else Tensor(jnp.asarray(np.asarray(data)))
    placements = _norm_placements(placements, mesh)
    sharding = _sharding_for(mesh, placements, t.ndim)
    partial_axes = [i for i, p in enumerate(placements) if isinstance(p, Partial)]
    if _state.trace_ctx is not None or isinstance(t._data, jax.core.Tracer):
        arr = jax.lax.with_sharding_constraint(unwrap(t), sharding)
    else:
        arr = jax.device_put(unwrap(t), sharding)
    out = Tensor(arr, stop_gradient=t.stop_gradient if stop_gradient is None else stop_gradient)
    out._grad_node, out._out_slot = t._grad_node, t._out_slot
    _set_dist_attr(out, mesh, placements)
    return out


def _set_dist_attr(t: Tensor, mesh, placements):
    # Tensor uses __slots__; dist attrs ride on the array's sharding + a registry
    _dist_attrs[id(t)] = (mesh, list(placements))


_dist_attrs: dict = {}


def get_placements(t: Tensor):
    if id(t) in _dist_attrs:
        return _dist_attrs[id(t)][1]
    sharding = getattr(t._data, "sharding", None)
    if isinstance(sharding, NamedSharding):
        mesh_names = list(sharding.mesh.axis_names)
        return spec_to_placements(sharding.spec, mesh_names, t.ndim)
    return None


def get_process_mesh(t: Tensor):
    if id(t) in _dist_attrs:
        return _dist_attrs[id(t)][0]
    sharding = getattr(t._data, "sharding", None)
    if isinstance(sharding, NamedSharding):
        m = sharding.mesh
        ids = np.arange(np.prod(m.devices.shape)).reshape(m.devices.shape)
        return ProcessMesh(ids, list(m.axis_names))
    return None


def reshard(dist_tensor, mesh: ProcessMesh, placements):
    """reference: auto_parallel/api.py:733 + the C++ reshard function library
    (phi/core/distributed/auto_parallel/reshard/) — all transitions (r_to_s,
    s_to_r, p_to_r, s_to_s, cross-mesh) collapse into one device_put /
    sharding_constraint; XLA emits the collectives."""
    placements = _norm_placements(placements, mesh)
    sharding = _sharding_for(mesh, placements, dist_tensor.ndim)
    arr = unwrap(dist_tensor)
    if _state.trace_ctx is not None or isinstance(arr, jax.core.Tracer):
        out_arr = jax.lax.with_sharding_constraint(arr, sharding)
    else:
        out_arr = jax.device_put(arr, sharding)
    out = Tensor(out_arr, stop_gradient=dist_tensor.stop_gradient)
    out._grad_node, out._out_slot = dist_tensor._grad_node, dist_tensor._out_slot
    _set_dist_attr(out, mesh, placements)
    return out


def shard_layer(layer, process_mesh: ProcessMesh, shard_fn=None, input_fn=None,
                output_fn=None):
    """reference: auto_parallel/api.py:844 — shard every parameter of a Layer."""
    def default_shard(name, sublayer, mesh):
        for pname, p in list(sublayer._parameters.items()):
            if p is None:
                continue
            sharded = shard_tensor(p, mesh, [Replicate() for _ in range(mesh.ndim)])
            p._data = sharded._data
    fn = shard_fn or default_shard
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, process_mesh)
    if input_fn is not None:
        layer.register_forward_pre_hook(lambda l, inp: input_fn(inp, process_mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(lambda l, inp, out: output_fn(out, process_mesh))
    return layer


def dtensor_from_fn(fn, mesh: ProcessMesh, placements, *args, **kwargs):
    t = fn(*args, **kwargs)
    return shard_tensor(t, mesh, placements)


def unshard_dtensor(dist_tensor):
    """Gather to replicated (reference: auto_parallel/api.py unshard_dtensor)."""
    arr = unwrap(dist_tensor)
    sharding = getattr(arr, "sharding", None)
    if isinstance(sharding, NamedSharding):
        out = jax.device_put(arr, NamedSharding(sharding.mesh, P()))
        t = Tensor(out, stop_gradient=dist_tensor.stop_gradient)
        return t
    return dist_tensor


def shard_optimizer(optimizer, shard_fn=None):
    """reference: auto_parallel/api.py shard_optimizer — accumulators follow the
    parameter shardings automatically on first access (our accumulators are
    created zeros_like the param, inheriting its sharding under jit)."""
    return optimizer


def local_map(fn, out_placements=None, in_placements=None, process_mesh=None,
              reshard_inputs=False):
    """Run fn on local shards via shard_map (reference: auto_parallel local_map)."""
    def wrapper(*tensors):
        from jax import shard_map
        mesh = (process_mesh or _global_mesh).jax_mesh()
        in_specs = tuple(placements_to_spec(p, t.ndim, list(mesh.axis_names))
                         for p, t in zip(in_placements, tensors))
        out_specs = placements_to_spec(out_placements[0], tensors[0].ndim,
                                       list(mesh.axis_names))
        f = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
        return Tensor(f(*[unwrap(t) for t in tensors]))
    return wrapper
