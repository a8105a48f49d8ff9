"""Blocked causal attention with an optional sliding window.

``banded_attention(q, k, v, window=None)`` is causal grouped-query
attention that does work in proportion to the pairs the mask allows: a
query block meets only the key blocks its window can reach (sliding
layers: T x window work) or the key blocks before it (full layers: the
causal half). One implementation, the window an argument, forward and
backward.

Layout: ``q`` (B, Hq, T, D), ``k`` / ``v`` (B, Hkv, T, D), ``Hq`` a
multiple of ``Hkv``; query head ``h`` reads key/value head ``h // G``.
A query at position ``t`` sees keys ``t - window + 1 .. t`` (all of
``0 .. t`` without a window). Scores and softmax are float32; the
probabilities meet ``v`` in ``v``'s dtype.

How it is blocked. One (batch, key/value head) pair at a time
(``lax.map``), so only one group's scores are alive; the pair's forward
is rematerialised in its backward (``jax.checkpoint``), so nothing of
size T x keys is kept between the passes and no (T, T) array of a head
is ever written:

- with a window, every query block of ``block`` rows meets the same
  number of key blocks (``ceil((window - 1) / block) + 1``), so all
  blocks are one batched product over a gathered band of keys;
- without one, query block *i* meets keys ``0 .. (i + 1) * block``: a
  loop over the query blocks, unrolled at trace time, each with a
  static slice of the keys.

On a TPU, where the shapes allow it (``splash_available``: head sizes of
128 lanes or multiples, and 64, zero-padded to the lanes), the same
mathematics runs as the Pallas splash-attention kernel that ships with
jax (block-sparse over the same mask, scores never leave fast memory);
``backend="xla"``
forces the composition above, which is also what the CPU runs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["banded_attention", "band_blocks", "default_backend"]

_NEG = -1e30  # a masked score: finite, so a row of them stays finite
# rows of a block where the caller names none, measured on a v5e at 8192
# tokens, 8 key/value heads of 128 (PR 28). The kernel: a window of 512
# is fastest at 512 with the backward pass as two kernels (10.9 ms
# forward and backward against 16.7 at 1024 fused), full causal
# attention at 1024 with the fused backward kernel (23.0 ms against 28.3
# unfused; 2048 does not fit the kernel's fast memory). The composition:
# 256, where a window of 512 meets 768 keys a query.
SPLASH_WINDOW_BLOCK, SPLASH_FULL_BLOCK, XLA_BLOCK = 512, 1024, 256


def band_blocks(window, block):
    """Key blocks a query block meets under ``window``."""
    return -(-(window - 1) // block) + 1


def _softmax_av(s, mask, v, eq):
    """Masked softmax of float32 scores ``s`` times ``v`` (``eq`` the
    einsum of probabilities and values)."""
    s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum(eq, p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o / denom


def _band_group(q, k, v, window, block, scale):
    """One key/value head: ``q`` (G, T, D), ``k`` / ``v`` (T, D), T a
    multiple of ``block``; every query block against its band."""
    g, t, d = q.shape
    nb, nk = t // block, band_blocks(window, block)

    def band(a):
        a = a.reshape(nb, block, d)
        a = jnp.concatenate(
            [jnp.zeros((nk - 1, block, d), a.dtype), a], axis=0)
        return jnp.concatenate([a[j:j + nb] for j in range(nk)], axis=1)

    kb, vb = band(k), band(v)                      # (nb, nk * block, D)
    qb = q.reshape(g, nb, block, d)
    s = jnp.einsum("gnqd,nkd->gnqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    qpos = (jnp.arange(nb) * block)[:, None, None] \
        + jnp.arange(block)[None, :, None]
    kpos = ((jnp.arange(nb) - (nk - 1)) * block)[:, None, None] \
        + jnp.arange(nk * block)[None, None, :]
    mask = (kpos <= qpos) & (qpos - kpos < window) & (kpos >= 0)
    o = _softmax_av(s, mask[None], vb, "gnqk,nkd->gnqd")
    return o.reshape(g, t, d).astype(q.dtype)


def _causal_group(q, k, v, block, scale):
    """One key/value head, no window: query block *i* against keys
    ``0 .. (i + 1) * block``."""
    g, t, d = q.shape
    outs = []
    for i in range(t // block):
        hi = (i + 1) * block
        qi = q[:, i * block:hi]
        s = jnp.einsum("gqd,kd->gqk", qi, k[:hi],
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(hi)[None, :] <= \
            (i * block + jnp.arange(block))[:, None]
        outs.append(_softmax_av(s, mask[None], v[:hi], "gqk,kd->gqd"))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _xla_attention(q, k, v, window, block, scale):
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    block = min(block, -(-t // 8) * 8)
    tp = -(-t // block) * block
    if tp != t:
        # rows past T see only themselves and are cut off again; keys
        # past T lie in no real query's past
        pad = [(0, 0), (0, 0), (0, tp - t), (0, 0)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    if window is not None and window < tp:
        group = functools.partial(_band_group, window=window, block=block,
                                  scale=scale)
    else:
        group = functools.partial(_causal_group, block=block, scale=scale)
    group = jax.checkpoint(group)
    qg = q.reshape(b * hkv, g, tp, d)
    kg, vg = k.reshape(b * hkv, tp, d), v.reshape(b * hkv, tp, d)
    o = jax.lax.map(lambda a: group(*a), (qg, kg, vg))
    return o.reshape(b, hq, tp, d)[:, :, :t]


# ---------------------------------------------------------------------------
# the Pallas kernel (TPU): jax's splash attention over the same mask
# ---------------------------------------------------------------------------

def splash_available(t, d) -> bool:
    """The kernel takes sequence lengths that are multiples of 128 and
    head sizes that are multiples of 128, or 64: a 64-wide head runs
    zero-padded to the 128 lanes (``_splash_attention``), which at 32
    query heads over 8 of 64, 8192 tokens, causal, forward and backward
    on a v5e took 15.7 ms against 17.6 for the composition below and
    17.5 for ``pallas_kernels.flash_attention`` over repeated key/value
    heads (``tools/attention_table.py --head64 1``; PERF.md section 6,
    PR 32). Narrower heads were not measured and take the
    composition."""
    return (d % 128 == 0 or d == 64) and t % 128 == 0 and t >= 128


def default_backend(t, d) -> str:
    """What ``banded_attention`` runs where the caller names no backend:
    the kernel on a TPU where the shapes allow it, else the
    composition."""
    return "splash" if (jax.default_backend() == "tpu"
                        and splash_available(t, d)) else "xla"


@functools.lru_cache(maxsize=None)
def _splash_kernel(g, t, window, block, interpret):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    banded = window is not None and window < t
    if banded:
        mask = sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0)
    else:
        mask = sm.CausalMask((t, t))
    if block is None:
        block = SPLASH_WINDOW_BLOCK if banded else SPLASH_FULL_BLOCK
    b = min(block, t)
    sizes = sk.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        # a band's backward pass is faster as two kernels (one more
        # recomputation of the scores, no sum of dq over the key blocks)
        **(dict(block_q_dq=b, block_kv_dq=b, use_fused_bwd_kernel=False)
           if banded else dict(use_fused_bwd_kernel=True)))
    return sk.make_splash_mqa_single_device(
        mask=sm.MultiHeadMask([mask] * g), block_sizes=sizes,
        interpret=interpret)


def _splash_attention(q, k, v, window, block, scale, interpret=False):
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kernel = _splash_kernel(g, t, window, block, interpret)
    q = q * jnp.asarray(scale, q.dtype)
    lanes = -(-d // 128) * 128
    if lanes != d:
        # a head narrower than the lanes: zeros past its dimensions add
        # nothing to a score and give result columns that are cut off
        pad = [(0, 0), (0, 0), (0, 0), (0, lanes - d)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    o = jax.vmap(jax.vmap(kernel))(q.reshape(b, hkv, g, t, lanes), k, v)
    return o.reshape(b, hq, t, lanes)[..., :d]


# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "block", "scale",
                                             "backend"))
def banded_attention(q, k, v, window=None, block=None, scale=None,
                     backend=None):
    """Causal grouped-query attention, keys no further back than
    ``window - 1`` positions (no limit with ``window=None``); see the
    module docstring. ``backend``: ``"xla"``, ``"splash"``
    (``"splash_interpret"``: the kernel interpreted, for tests off the
    chip) or None (the kernel on a TPU where the shapes allow it, else
    the composition).
    ``block``: rows of a query block (and of a key block); None takes
    the backend's own (``SPLASH_*_BLOCK``, ``XLA_BLOCK``)."""
    if q.shape[1] % k.shape[1]:
        raise ValueError("query heads must be a multiple of key/value "
                         f"heads, got {q.shape[1]} and {k.shape[1]}")
    if window is not None and window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    if backend is None:
        backend = default_backend(*q.shape[2:])
    if backend in ("splash", "splash_interpret"):
        return _splash_attention(q, k, v, window, block, scale,
                                 interpret=backend == "splash_interpret")
    return _xla_attention(q, k, v, window, block or XLA_BLOCK, scale)
