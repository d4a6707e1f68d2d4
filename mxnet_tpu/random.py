"""Framework RNG: a counter-based PRNG stream over jax.random keys.

Reference: per-context RNG resources (``ResourceRequest::kRandom``,
``src/resource.cc``, ``MXNET_SEED`` — TBV, SURVEY.md §2.1/§5.6). TPU-native
redesign: JAX's splittable threefry keys replace per-device curand states.

Two regimes:
- **Eager:** a process-global key advanced (split) per draw; seeded by
  ``mx.random.seed(n)`` / env ``MXNET_SEED``.
- **Traced (hybridize / jit):** the jitted step function takes the key as an
  argument; a trace-scope installs that traced key here, and each draw
  ``fold_in``s a call-site counter — so the compiled function is pure and the
  stream is reproducible across replays.
"""
from __future__ import annotations

import threading

import jax
import numpy as np

from .base import get_env

__all__ = ["seed", "next_key", "trace_key_scope", "get_state",
           "get_state_data", "set_state_data", "uniform", "normal",
           "randint", "randn", "bernoulli", "gamma", "exponential", "poisson",
           "negative_binomial", "generalized_negative_binomial", "multinomial",
           "shuffle"]


class _KeyState(threading.local):
    def __init__(self):
        self.key = None
        self.trace_key = None
        self.trace_counter = 0
        self.np_rng = None


_STATE = _KeyState()


def np_rng() -> np.random.Generator:
    """Host-side numpy generator tied to the framework seed. Used by
    initializers so ``mx.random.seed(n)`` makes parameter init reproducible
    (reference behavior: initializers draw from the seeded MXNet RNG)."""
    if _STATE.np_rng is None:
        s = get_env("MXNET_SEED", None, int)
        _STATE.np_rng = np.random.default_rng(s)
    return _STATE.np_rng


def _root_key():
    if _STATE.key is None:
        s = get_env("MXNET_SEED", None, int)
        _STATE.key = jax.random.key(s if s is not None else np.random.randint(0, 2**31))
    return _STATE.key


def seed(seed_state: int, ctx="all") -> None:
    """Seed the global stream (reference mx.random.seed; MXNET_SEED env)."""
    _STATE.key = jax.random.key(int(seed_state))
    _STATE.np_rng = np.random.default_rng(int(seed_state))


def next_key():
    """Next PRNG key. Trace-safe: inside a trace scope, folds a counter into
    the traced key instead of advancing global state."""
    if _STATE.trace_key is not None:
        _STATE.trace_counter += 1
        return jax.random.fold_in(_STATE.trace_key, _STATE.trace_counter)
    k = _root_key()
    _STATE.key, sub = jax.random.split(k)
    return sub


class trace_key_scope:
    """Install a (possibly traced) key as the draw source, e.g. inside CachedOp."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        self.saved = (_STATE.trace_key, _STATE.trace_counter)
        _STATE.trace_key = self.key
        _STATE.trace_counter = 0
        return self

    def __exit__(self, *exc):
        _STATE.trace_key, _STATE.trace_counter = self.saved


def get_state():
    return _root_key()


def get_state_data():
    """Serializable view of the global key stream (checkpoint capture):
    the raw uint32 key data, or None when the stream was never seeded/used
    (a resumed process will lazily seed exactly like a fresh one)."""
    if _STATE.key is None:
        return None
    return np.asarray(jax.random.key_data(_STATE.key))


def set_state_data(data) -> None:
    """Restore the stream captured by :func:`get_state_data` (checkpoint
    resume) — draws after this replay bit-identically."""
    _STATE.key = jax.random.wrap_key_data(np.asarray(data, np.uint32))


# ---------------------------------------------------------------------------
# Sampling front-ends (mx.random.* / mx.nd.random.*). Reference:
# src/operator/random/sample_op.* (TBV). Return NDArray.
# ---------------------------------------------------------------------------

def _as_nd(arr, ctx=None):
    from .ndarray import NDArray

    return NDArray(arr, ctx=ctx)


def _shape(shape):
    if shape is None:
        return ()
    return (shape,) if isinstance(shape, int) else tuple(shape)


def uniform(low=0.0, high=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    import jax.numpy as jnp

    from .base import dtype_np

    r = jax.random.uniform(next_key(), _shape(shape), dtype_np(dtype), low, high)
    return _store(out, r, ctx)


def normal(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    from .base import dtype_np

    r = loc + scale * jax.random.normal(next_key(), _shape(shape), dtype_np(dtype))
    return _store(out, r, ctx)


def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None, **kw):
    return normal(loc, scale, shape, dtype, ctx)


def randint(low, high=None, shape=None, dtype="int32", ctx=None, out=None, **kw):
    from .base import dtype_np

    if high is None:
        low, high = 0, low
    r = jax.random.randint(next_key(), _shape(shape), int(low), int(high), dtype_np(dtype))
    return _store(out, r, ctx)


def bernoulli(prob=0.5, shape=None, dtype="float32", ctx=None, out=None, **kw):
    from .base import dtype_np

    r = jax.random.bernoulli(next_key(), prob, _shape(shape)).astype(dtype_np(dtype))
    return _store(out, r, ctx)


def gamma(alpha=1.0, beta=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    from .base import dtype_np

    r = jax.random.gamma(next_key(), alpha, _shape(shape), dtype_np(dtype)) * beta
    return _store(out, r, ctx)


def exponential(scale=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    from .base import dtype_np

    r = jax.random.exponential(next_key(), _shape(shape), dtype_np(dtype)) * scale
    return _store(out, r, ctx)


def poisson(lam=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    from .base import dtype_np

    r = jax.random.poisson(next_key(), lam, _shape(shape)).astype(dtype_np(dtype))
    return _store(out, r, ctx)


def negative_binomial(k=1, p=1.0, shape=None, dtype="float32", ctx=None, out=None, **kw):
    g = jax.random.gamma(next_key(), k, _shape(shape)) * ((1 - p) / p)
    from .base import dtype_np

    r = jax.random.poisson(next_key(), g).astype(dtype_np(dtype))
    return _store(out, r, ctx)


def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=None, dtype="float32",
                                  ctx=None, out=None, **kw):
    import jax.numpy as jnp

    a = 1.0 / max(alpha, 1e-12)
    g = jax.random.gamma(next_key(), a, _shape(shape)) * (mu / a)
    from .base import dtype_np

    r = jax.random.poisson(next_key(), g).astype(dtype_np(dtype))
    return _store(out, r, ctx)


def multinomial(data, shape=1, get_prob=False, dtype="int32", **kw):
    """Sample class indices from probability rows; with get_prob=True also
    return log-probabilities of the draws (reinforce-style usage)."""
    import jax.numpy as jnp

    from .base import dtype_np
    from .ndarray import NDArray

    probs = data.asjax() if isinstance(data, NDArray) else jnp.asarray(data)
    n = int(np.prod(_shape(shape))) if not isinstance(shape, int) else int(shape)
    logits = jnp.log(jnp.maximum(probs, 1e-38))
    if probs.ndim == 1:
        draws = jax.random.categorical(next_key(), logits, shape=(n,))  # (n,)
    else:
        draws = jax.vmap(lambda lg, k: jax.random.categorical(k, lg, shape=(n,)))(
            logits, jax.random.split(next_key(), probs.shape[0]))  # (B, n)
    tail = _shape(shape) if not isinstance(shape, int) else ((shape,) if shape != 1 else ())
    out_shape = (probs.shape[:1] + tail) if probs.ndim > 1 else tail
    result = draws.reshape(out_shape) if out_shape else draws.reshape(())
    if get_prob:
        logp = jax.nn.log_softmax(logits, axis=-1)
        if probs.ndim == 1:
            lp = logp[draws]  # (n,)
        else:
            lp = jnp.take_along_axis(logp, draws.astype(jnp.int32), axis=-1)  # (B, n)
        lp = lp.reshape(out_shape) if out_shape else lp.reshape(())
        return _as_nd(result.astype(dtype_np(dtype))), _as_nd(lp)
    return _as_nd(result.astype(dtype_np(dtype)))


def shuffle(data, **kw):
    from .ndarray import NDArray

    arr = data.asjax() if isinstance(data, NDArray) else data
    perm = jax.random.permutation(next_key(), arr.shape[0])
    return _as_nd(arr[perm])


def _store(out, arr, ctx):
    if out is not None:
        out._set_data(arr)
        return out
    return _as_nd(arr, ctx)
