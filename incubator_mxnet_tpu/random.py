"""Global RNG state with a trace-aware key provider.

Re-design of the reference RNG resources (SURVEY.md §2.1 "Resource
manager", §2.3 "Random"; ref `src/common/random_generator.cu`,
`src/operator/random/sample_op.cc` [UNVERIFIED]): instead of per-device
stateful generators handed to ops, we use JAX's counter-based
threefry keys — reproducible by construction.

Eager mode: a global key is split per call (``mx.random.seed`` parity).
Trace mode (inside ``hybridize()``): a *key provider* holding a traced
key is installed; calls take ``fold_in(base_key, counter)`` so the
compiled program is parametric in the key — fresh randomness per step
without retracing (SURVEY.md §7 hard part #1's RNG corollary).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["seed", "next_key", "uniform", "normal", "randint", "randn",
           "TraceKeyProvider", "get_state", "set_state"]


class _RngState(threading.local):
    """Global key state — created LAZILY: materializing a PRNGKey at
    import time would initialize the XLA backend before a worker can
    call jax.distributed.initialize (tools/launch.py flow)."""

    def __init__(self):
        self._key = None
        self.provider = None
        self.cache = None  # pre-split key block (amortizes split dispatch)
        self.cache_pos = 0
        self.step_counter = 0

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(0)
        return self._key

    @key.setter
    def key(self, v):
        self._key = v


_STATE = _RngState()

_CACHE_BLOCK = 64


class TraceKeyProvider:
    """Deterministic key stream derived from one (possibly traced) key."""

    def __init__(self, base_key):
        self.base_key = base_key
        self.counter = 0

    def next_key(self):
        k = jax.random.fold_in(self.base_key, self.counter)
        self.counter += 1
        return k

    def __enter__(self):
        self._old = _STATE.provider
        _STATE.provider = self
        return self

    def __exit__(self, *a):
        _STATE.provider = self._old


def key_to_seed(key):
    """Collapse a threefry key (uint32[2]) to the (1,) int32 seed the
    in-kernel TPU PRNG consumes (`ops.dropout_kernel.fused_dropout`).
    Works on traced keys — the jitted program stays key-parametric."""
    k = jnp.asarray(key).astype(jnp.uint32).reshape(-1)
    return (k[0] ^ k[-1]).astype(jnp.int32).reshape(1)


def seed(seed_state: int, ctx=None):
    _STATE.key = jax.random.PRNGKey(int(seed_state))
    _STATE.cache = None
    _STATE.cache_pos = 0
    _STATE.step_counter = 0


def next_key():
    if _STATE.provider is not None:
        return _STATE.provider.next_key()
    # split a block at a time: one device dispatch per _CACHE_BLOCK keys
    # (the eager per-call split costs ~1.5ms/step in training loops)
    if _STATE.cache is None or _STATE.cache_pos >= _CACHE_BLOCK:
        keys = jax.random.split(_STATE.key, _CACHE_BLOCK + 1)
        _STATE.key = keys[0]
        _STATE.cache = keys[1:]
        _STATE.cache_pos = 0
    sub = _STATE.cache[_STATE.cache_pos]
    _STATE.cache_pos += 1
    return sub


def step_key():
    """(base_key, counter) pair for compiled step programs.

    The base key array is STABLE across calls (no device dispatch per
    step); the python counter advances and is folded into the key
    inside the jitted program — fresh randomness per step with zero
    eager RNG ops.

    Provider-aware (r5 fix): when a TraceKeyProvider is active we are
    INSIDE another cached program's trace (a hybridized child called
    from a hybridized parent's apply_fn).  Reading the global state
    there would bake the CONCRETE (key, counter) into the parent's
    jaxpr as constants — every replay of the parent program would
    reuse the same dropout masks (measured: nested-block dropout was
    step-constant).  Drawing from the provider instead yields a key
    derived from the parent's TRACED key, so the composed program
    stays key-parametric end to end.
    """
    if _STATE.provider is not None:
        return _STATE.provider.next_key(), 0
    _STATE.step_counter = getattr(_STATE, "step_counter", 0) + 1
    return _STATE.key, _STATE.step_counter


def get_state():
    """Full RNG state: (key, step_counter) — both are needed to replay a
    hybridized training run (step_key folds the counter per step)."""
    return (_STATE.key, getattr(_STATE, "step_counter", 0))


def set_state(state):
    if isinstance(state, tuple) and len(state) == 2:
        _STATE.key, _STATE.step_counter = state
    else:  # bare key (older snapshots): restart the step stream
        _STATE.key = state
        _STATE.step_counter = 0
    _STATE.cache = None
    _STATE.cache_pos = 0


# convenience module-level samplers (mx.random.uniform parity)
def uniform(low=0.0, high=1.0, shape=(1,), dtype="float32", ctx=None):
    from .ndarray.ndarray import NDArray

    return NDArray(jax.random.uniform(next_key(), tuple(shape) if not isinstance(shape, int) else (shape,),
                                      minval=low, maxval=high, dtype=jnp.dtype(dtype)))


def normal(loc=0.0, scale=1.0, shape=(1,), dtype="float32", ctx=None):
    from .ndarray.ndarray import NDArray

    shp = tuple(shape) if not isinstance(shape, int) else (shape,)
    return NDArray(loc + scale * jax.random.normal(next_key(), shp, dtype=jnp.dtype(dtype)))


def randn(*shape, dtype="float32", ctx=None):
    return normal(0.0, 1.0, shape or (1,), dtype=dtype)


def randint(low, high=None, shape=(1,), dtype="int32", ctx=None):
    from .ndarray.ndarray import NDArray

    if high is None:
        low, high = 0, low
    shp = tuple(shape) if not isinstance(shape, int) else (shape,)
    return NDArray(jax.random.randint(next_key(), shp, low, high, dtype=jnp.dtype(dtype)))
