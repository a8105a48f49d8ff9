"""Random state: stateless threefry keys behind a stateful-looking API.

TPU-native replacement for the reference RNG (ref:
include/mxnet/random_generator.h — 1024 mt19937 CPU states / Philox GPU
states seeded through the resource manager, src/resource.cc). On TPU the
natural design is JAX's counter-based threefry: a single root key advanced
by splitting. `trace_key` supports jit-captured graphs (CachedOp/hybridize):
during tracing, keys derive from a key *argument* of the compiled function
via fold_in, so each execution gets fresh randomness without retracing.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp


class _RNGState(threading.local):
    """LAZY root key: creating a jax key materializes a device array,
    which initializes the backend — far too early at import time
    (helper processes must pick their platform first, e.g. spawn
    DataLoader workers, which must never open the parent's chip)."""

    def __init__(self):
        self._key = None
        self.trace_key = None
        self.trace_counter = 0

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(0)
        return self._key

    @key.setter
    def key(self, k):
        self._key = k


_STATE = _RNGState()


def seed(seed_state: int, ctx=None):
    """ref: python/mxnet/random.py seed → MXRandomSeed"""
    _STATE.key = jax.random.key(int(seed_state))


def next_key():
    if _STATE.trace_key is not None:
        _STATE.trace_counter += 1
        return jax.random.fold_in(_STATE.trace_key, _STATE.trace_counter)
    new_key, sub = jax.random.split(_STATE.key)
    if isinstance(new_key, jax.core.Tracer):
        # inside a jit trace with no explicit key argument (e.g. a plain
        # jax.jit around an inference forward): never store a tracer in
        # the global state — derive a constant per-trace key instead
        _STATE.trace_counter += 1
        return jax.random.fold_in(jax.random.key(0), _STATE.trace_counter)
    _STATE.key = new_key
    return sub


class trace_rng:
    """Scope used by CachedOp tracing: keys derive from `key_arg`."""

    def __init__(self, key_arg):
        self.key_arg = key_arg

    def __enter__(self):
        self._saved = (_STATE.trace_key, _STATE.trace_counter)
        _STATE.trace_key = self.key_arg
        _STATE.trace_counter = 0
        return self

    def __exit__(self, *exc):
        _STATE.trace_key, _STATE.trace_counter = self._saved


# ---------------------------------------------------------------------------
# user-facing samplers (ref: python/mxnet/ndarray/random.py; kernels in
# src/operator/random/sample_op.cc)
# ---------------------------------------------------------------------------

def _sample(fn, shape, ctx, dtype, **kw):
    from .ndarray.ndarray import _wrap, _place, _canon_dtype
    shape = (shape,) if isinstance(shape, int) else tuple(shape or ())
    arr = fn(next_key(), shape=shape, **kw)
    if dtype is not None:
        arr = arr.astype(_canon_dtype(dtype))
    return _wrap(_place(arr, ctx))


def uniform(low=0.0, high=1.0, shape=(1,), dtype="float32", ctx=None, out=None, **kw):
    return _sample(lambda k, shape: jax.random.uniform(
        k, shape, minval=low, maxval=high), shape, ctx, dtype)


def normal(loc=0.0, scale=1.0, shape=(1,), dtype="float32", ctx=None, out=None, **kw):
    return _sample(lambda k, shape: loc + scale * jax.random.normal(k, shape),
                   shape, ctx, dtype)


randn = normal


def gamma(alpha=1.0, beta=1.0, shape=(1,), dtype="float32", ctx=None, out=None, **kw):
    return _sample(lambda k, shape: jax.random.gamma(k, alpha, shape) * beta,
                   shape, ctx, dtype)


def exponential(scale=1.0, shape=(1,), dtype="float32", ctx=None, out=None, **kw):
    return _sample(lambda k, shape: jax.random.exponential(k, shape) * scale,
                   shape, ctx, dtype)


def poisson(lam=1.0, shape=(1,), dtype="float32", ctx=None, out=None, **kw):
    return _sample(lambda k, shape: jax.random.poisson(k, lam, shape=shape),
                   shape, ctx, dtype)


def negative_binomial(k=1, p=0.5, shape=(1,), dtype="float32", ctx=None, **kw):
    def f(key, shape):
        g = jax.random.gamma(key, k, shape) * (1 - p) / p
        return jax.random.poisson(jax.random.fold_in(key, 1), g, shape=shape)
    return _sample(f, shape, ctx, dtype)


def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=(1,), dtype="float32",
                                  ctx=None, **kw):
    def f(key, shape):
        r = 1.0 / alpha
        p = r / (r + mu)
        g = jax.random.gamma(key, r, shape) * (1 - p) / p
        return jax.random.poisson(jax.random.fold_in(key, 1), g, shape=shape)
    return _sample(f, shape, ctx, dtype)


def randint(low, high, shape=(1,), dtype="int32", ctx=None, out=None, **kw):
    return _sample(lambda k, shape: jax.random.randint(k, shape, low, high),
                   shape, ctx, dtype)


def multinomial(data, shape=None, get_prob=False, dtype="int32", **kw):
    """ref: src/operator/random/sample_multinomial_op.cc"""
    from .ndarray.ndarray import NDArray, _wrap
    logits = jnp.log(jnp.clip(data._data, 1e-20, None))
    n = 1 if shape is None else (shape if isinstance(shape, int) else int(onp.prod(shape)))
    if logits.ndim == 1:
        samp = jax.random.categorical(next_key(), logits, shape=(n,))
        if shape is None:
            samp = samp.reshape(())
    else:
        samp = jax.random.categorical(next_key(), logits[:, None, :],
                                      axis=-1, shape=(logits.shape[0], n))
        if shape is None:
            samp = samp.squeeze(-1)
    samp = samp.astype(jnp.dtype(dtype))
    if get_prob:
        lp = jnp.take_along_axis(jax.nn.log_softmax(data._data if False else logits, axis=-1),
                                 samp[..., None].astype(jnp.int32), axis=-1).squeeze(-1)
        return _wrap(samp), _wrap(lp)
    return _wrap(samp)


def shuffle(data, **kw):
    from .ndarray.ndarray import _wrap
    return _wrap(jax.random.permutation(next_key(), data._data, axis=0))


def bernoulli(prob=0.5, shape=(1,), dtype="float32", ctx=None, **kw):
    return _sample(lambda k, shape: jax.random.bernoulli(k, prob, shape),
                   shape, ctx, dtype)
