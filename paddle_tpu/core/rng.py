"""RNG — stateful seed surface over JAX functional keys.

Analog of phi::Generator (phi/core/generator.h): Paddle exposes a global stateful
seed; JAX wants explicit threaded keys. The bridge: the generator's key lives inside
a Tensor, so reads/writes go through dispatch and program capture lifts the key to a
program input / mutated output automatically — random ops under to_static get a fresh
key every call instead of a baked constant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .tensor import Tensor
from . import dispatch


class Generator:
    """Key creation is LAZY: ``PRNGKey`` is a device op, and building it in
    ``__init__`` would initialize the jax backend at ``import paddle_tpu``
    time — and a process that has initialized the backend holds the chip, so
    a CLI that only parses arguments and starts workers (the launcher, a
    fleet supervisor) would take it away from them."""

    def __init__(self, seed: int = 0):
        self._state = None
        self._seed = seed

    def _ensure_state(self):
        if self._state is None:
            self._state = Tensor(jax.random.PRNGKey(self._seed),
                                 persistable=True)
            self._state.name = "global_rng_state"
        return self._state

    def manual_seed(self, seed: int):
        self._seed = seed
        if self._state is not None:
            # in-place so captured programs that lifted the state Tensor as a
            # program input keep seeing this generator's stream
            self._state._data = jax.random.PRNGKey(seed)
        return self

    def get_state(self) -> Tensor:
        return self._ensure_state()

    def set_state(self, state: Tensor):
        data = state._data if isinstance(state, Tensor) else jnp.asarray(state)
        if self._state is None:
            # build the Tensor straight from the incoming state — going via
            # _ensure_state would run a throwaway PRNGKey device op
            self._state = Tensor(data, persistable=True)
            self._state.name = "global_rng_state"
        else:
            self._state._data = data

    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        """Split the state key; returns a fresh subkey (array)."""
        key = dispatch.unwrap(self._ensure_state())
        new_state, sub = jax.random.split(key)
        self._state._data = new_state
        return sub


_default_generator = Generator(0)


def default_generator() -> Generator:
    return _default_generator


def seed(s: int) -> Generator:
    """paddle.seed analog."""
    _default_generator.manual_seed(int(s))
    return _default_generator


def get_rng_state():
    return [_default_generator.get_state()]


def set_rng_state(states):
    _default_generator.set_state(states[0] if isinstance(states, (list, tuple)) else states)


def next_key():
    return _default_generator.next_key()
