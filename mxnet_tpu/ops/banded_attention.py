"""Blocked causal attention with an optional sliding window.

``banded_attention(q, k, v, window=None)`` is causal grouped-query
attention that does work in proportion to the pairs the mask allows: a
query block meets only the key blocks its window can reach (sliding
layers: T x window work) or the key blocks before it (full layers: the
causal half). One implementation, the window an argument, forward and
backward.

Layout: ``q`` (B, Hq, T, D), ``k`` / ``v`` (B, Hkv, T, D), ``Hq`` a
multiple of ``Hkv``; query head ``h`` reads key/value head ``h // G``
(``banded_attention_token_major``: the same with T before the heads).
``v``'s head size may differ from ``q``'s and ``k``'s (latent
attention: 192-wide scores over 128-wide values); the result has
``v``'s.
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

What runs where. On the CPU, and wherever no kernel takes the shapes,
the composition above (``backend="xla"`` forces it). On a TPU:

- full causal attention (no window, or one that reaches the whole
  sequence), and a window the band kernel does not take: the Pallas
  splash-attention kernel that ships with jax (block-sparse over the
  same mask, scores never leave fast memory) where ``splash_available``
  says so: head sizes of 128 lanes or multiples, and 64 and 192,
  zero-padded to the lanes;
- a sliding window on heads as the projections leave them
  (``banded_attention_token_major``: (B, T, H, D), no transpose on
  either side) where ``band_available`` says so: this repo's band
  kernel, below.

The band kernel. One grid step is one query block of one key/value
head's group of query heads; the block's band of keys (the block before
and the block itself) lies whole in fast memory, carried from step to
step, so each key block is fetched once. Inside the step the query
block goes through in sub-blocks of ``BAND_SUB`` rows, each against the
``window + BAND_SUB`` keys its rows can see, with the mask on the first
and the last ``BAND_SUB`` keys only: 1.25 times the allowed pairs at a
window of 512, where a block-sparse kernel at blocks of 512 computes
twice. The group's heads go through each product together, stacked as
rows, so the keys they share are loaded into the matrix unit once. One
plain softmax a row (the whole band is there); the logsumexp is the
only residual beside the operands. The backward pass is ONE kernel over
the same grid with the scores keys-by-queries (the row statistics are
rows; of its five products only dq wants an operand turned): p and dp
once, the softmax's backward sum from p * dp, dq whole, dk and dv
summed over the group's heads inside their products and over the two
query blocks that see a key block in a buffer carried one step on.
Asked to (``rotary=``, ``gate=``), the same two kernels turn q and k by
their rotary positions as they read them and multiply each head's
result by the sigmoid of its gate logit as they write it, with the
transposed operations in the backward kernel, so that a sliding layer's
block between its projections is the two kernels and nothing else: XLA
lays a (B, T, H, D) array out by (H, D) tiles and a (B, T, H * D) one by
(T, H * D) tiles, and every elementwise pass between the two layouts
costs a relayout of q's size (PERF.md section 6, PR 34).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["banded_attention", "banded_attention_token_major",
           "band_available", "band_blocks", "default_backend"]

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
        a = a.reshape(nb, block, a.shape[-1])
        a = jnp.concatenate(
            [jnp.zeros((nk - 1,) + a.shape[1:], a.dtype), a], axis=0)
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
    return o.reshape(g, t, v.shape[-1]).astype(q.dtype)


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
    kg, vg = k.reshape(b * hkv, tp, d), v.reshape(b * hkv, tp, v.shape[-1])
    o = jax.lax.map(lambda a: group(*a), (qg, kg, vg))
    return o.reshape(b, hq, tp, v.shape[-1])[:, :, :t]


# ---------------------------------------------------------------------------
# the Pallas kernel (TPU): jax's splash attention over the same mask
# ---------------------------------------------------------------------------

def _pads_to_lanes(d):
    return d % 128 == 0 or d in (64, 192)


def splash_available(t, d, dv=None) -> bool:
    """The kernel takes sequence lengths that are multiples of 128 and
    head sizes (``d`` of q and k, ``dv`` of v where it differs) that are
    multiples of 128, or 64, or 192: a narrower head runs zero-padded to
    whole lanes of 128 (``_splash_attention``). 64: at 32 query heads
    over 8 of 64, 8192 tokens, causal, forward and backward on a v5e
    the padded kernel took 15.7 ms against 17.6 for the composition
    below and 17.5 for ``pallas_kernels.flash_attention`` over repeated
    key/value heads (``tools/attention_table.py --head64 1``; PERF.md
    section 6, PR 32). 192 over values of 128 (latent attention: 32
    heads, 4096 tokens, causal, forward and backward): q and k padded
    to 256 lanes, the values' width kept, 8.03 ms alone and 8.23
    between the projections against 11.38 and 13.39 for the composition
    (``tools/attention_table.py --latent 1``; PERF.md section 6, PR
    35). Other head sizes were not measured and take the
    composition."""
    return _pads_to_lanes(d) and _pads_to_lanes(dv or d) \
        and t % 128 == 0 and t >= 128


def default_backend(t, d, window=None, group=1, dv=None) -> str:
    """What runs where the caller names no backend: on a TPU the band
    kernel for a ``window`` and a ``group`` it takes (``band_available``;
    token-major callers alone say theirs here), the splash kernel where
    the shapes allow it (``dv``: the values' head size where it is not
    ``d``), else the composition."""
    if jax.default_backend() != "tpu":
        return "xla"
    if band_available(t, d, window, group) and dv in (None, d):
        return "band"
    return "splash" if splash_available(t, d, dv) else "xla"


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
    dv = v.shape[-1]

    def to_lanes(a):
        # a head that fills no whole lanes: zeros past its dimensions
        # add nothing to a score and give result columns that are cut
        # off
        short = -a.shape[-1] % 128
        return jnp.pad(a, [(0, 0)] * 3 + [(0, short)]) if short else a

    q, k, v = to_lanes(q), to_lanes(k), to_lanes(v)
    o = jax.vmap(jax.vmap(kernel))(
        q.reshape(b, hkv, g, t, q.shape[-1]), k, v)
    return o.reshape(b, hq, t, v.shape[-1])[..., :dv]


# ---------------------------------------------------------------------------
# the band kernel (TPU): a sliding window's layers, token-major
# ---------------------------------------------------------------------------

# rows of a query block and of the sub-blocks a grid step cuts it into
# (a sub-block's statistics are one row of 128 lanes; its band is 1.25
# windows at a window of 512). Measured on a v5e at the Laguna cell's
# window layer (8192 tokens, 64 query heads over 8 key/value heads of
# 128, window 512, bfloat16, forward plus backward, device ms a call
# alone / between a q/k/v and an output projection;
# tools/attention_table.py --window 1; PERF.md section 6, PR 34), the
# sub-blocks unrolled: 512 rows 3.63 / 4.72, 1024 rows 3.69 / 4.78, 2048
# rows 4.07 / 5.17; as a loop 3.71 / 4.80, 3.74 / 4.83, 3.83 / 4.92;
# jax's splash kernel at blocks of 512 11.9 / 13.0. With one head a grid
# step (the group's heads as a fourth grid dimension, sub-blocks of 128
# / 256 rows) the same kernel read 7.5 / 6.6 ms alone at 512 rows and
# 7.0 / 6.1 at 1024: stacking the group's heads through each product is
# what fills the matrix unit.
BAND_BLOCK, BAND_SUB = 512, 128
# a group's scores of one sub-block, grp * BAND_SUB rows by
# window + BAND_SUB keys in float32 several times over, lie in fast
# memory: the largest that were compiled for a v5e
BAND_MAX_WINDOW, BAND_MAX_SCORES = 1024, 16 * BAND_SUB * 640


def _band_fits(t, d, window, block):
    return (window is not None and window < t and d % 128 == 0
            and window % BAND_SUB == 0 and block % BAND_SUB == 0
            and window <= block and t % block == 0)


def band_available(t, d, window, group=1) -> bool:
    """The band kernel takes a window shorter than the sequence, whole
    sub-blocks long (``BAND_SUB``) and at most ``BAND_MAX_WINDOW``, a
    sequence of whole query blocks (``BAND_BLOCK`` rows, or the window
    where that is longer: a query block's band lies in its own and the
    block before), a head size of whole lane widths, and a ``group``
    (query heads a key/value head) whose scores of one sub-block fit
    fast memory (``BAND_MAX_SCORES``)."""
    return (window is not None and window <= BAND_MAX_WINDOW
            and group * BAND_SUB * (window + BAND_SUB) <= BAND_MAX_SCORES
            and _band_fits(t, d, window, max(BAND_BLOCK, window)))


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
_F32 = jnp.float32


def _dot(a, b, dims, exact=False):
    """One pass of the matrix unit, float32 accumulation; ``exact``:
    float32 operands at full precision (a permutation of them)."""
    precision = jax.lax.Precision.HIGHEST \
        if exact and a.dtype == _F32 else None
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _rotated(x, cos, sin, turn):
    """Rotary positions on the rows of ``x`` (n, D) as the models apply
    them: ``x * cos + (x @ turn) * sin`` (``turn`` the signed
    permutation that is rotate-half), ``cos`` / ``sin`` (n, D) float32;
    float32 arithmetic, one rounding to ``x``'s dtype."""
    turned = _dot(x, turn, _NN, exact=True)
    return (x.astype(_F32) * cos + turned * sin).astype(x.dtype)


def _unrotated(g, cos, sin, turn):
    """``_rotated`` transposed: ``g * cos + (g * sin) @ turn.T``."""
    back = _dot((g.astype(_F32) * sin).astype(g.dtype), turn, _NT,
                exact=True)
    return (g.astype(_F32) * cos + back).astype(g.dtype)


def _edge_masks(grp, axis):
    """(keep under the window, keep under the causal mask) for the first
    and the last ``BAND_SUB`` keys of a sub-block's band: keys along
    ``axis`` and, along the other, the sub-block's queries of each of
    the group's ``grp`` heads, head after head."""
    sb = BAND_SUB
    shape = (grp * sb, sb) if axis == 1 else (sb, grp * sb)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - axis) % sb
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return k_idx > q_idx, k_idx <= q_idx


def _masked(s, axis, keeps, lo, bq, first):
    """Scores of one sub-block against its band (keys along ``axis``,
    rows ``lo ..`` of the band buffer): the window's triangle on the
    first ``BAND_SUB`` keys, the causal one on the last; what lies
    between is whole. In the sequence's ``first`` block nothing lies
    before position 0, row ``bq`` of the buffer."""
    sb, n = BAND_SUB, s.shape[axis]

    def cut(a, b):
        return jax.lax.slice_in_dim(s, a, b, axis=axis)

    parts = [jnp.where(keeps[0], cut(0, sb), _NEG)]
    if n > 2 * sb:
        parts.append(cut(sb, n - sb))
    parts.append(jnp.where(keeps[1], cut(n - sb, n), _NEG))
    s = jnp.concatenate(parts, axis=axis)
    if first:
        shape = (1, n) if axis == 1 else (n, 1)
        row = lo + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        s = jnp.where(row >= bq, s, _NEG)
    return s


def _next_block(i, bq, refs_and_blocks):
    """The band buffers one block on: the block itself becomes the block
    before (zeros in the sequence's first block, which has none),
    ``new`` the block itself."""
    for ref, new in refs_and_blocks:
        @pl.when(i == 0)
        def _none_before():
            ref[:bq] = jnp.zeros((bq,) + ref.shape[1:], ref.dtype)

        @pl.when(i > 0)
        def _shift():
            ref[:bq] = ref[bq:]

        ref[bq:] = new


def _sub_blocks(i, nq, bq, window, sub_block):
    """``sub_block(r0, lo, first)`` for every sub-block of query block
    ``i``: rows ``r0 ..`` of the block against rows ``lo ..`` of the
    band buffer, which holds the block before in its rows ``0 .. bq``
    and the block itself after them. Unrolled, so that one sub-block's
    products overlap the next one's softmax (2 ms a step in the Laguna
    cell against a loop); the sequence's first block, one step of
    T / bq, is a loop: half the code to compile."""
    @pl.when(i == 0)
    def _first():
        def step(j, carry):
            r0 = pl.multiple_of(j * BAND_SUB, BAND_SUB)
            sub_block(r0, pl.multiple_of(r0 + bq - window, BAND_SUB), True)
            return carry
        jax.lax.fori_loop(0, bq // BAND_SUB, step, 0)

    if nq > 1:
        @pl.when((i > 0) & (i < nq))
        def _rest():
            for r0 in range(0, bq, BAND_SUB):
                sub_block(r0, r0 + bq - window, False)


class _Group:
    """What the two kernels share of one grid step (a key/value head's
    group of ``grp`` query heads, a query block): the group's rows of a
    sub-block head after head, the rotary tables and the gate beside
    them."""

    def __init__(self, grp, d, scale, rotary_refs, gate_ref):
        self.grp, self.d, self.scale = grp, d, scale
        self.rotary, self.gate_ref = rotary_refs, gate_ref

    def rows(self, ref, r0):
        """Rows ``r0 .. r0 + BAND_SUB`` of each head of the group
        (column blocks of ``d``): (grp * BAND_SUB, d)."""
        return jnp.concatenate(
            [ref[0, pl.ds(r0, BAND_SUB), g * self.d:(g + 1) * self.d]
             for g in range(self.grp)], axis=0)

    def put(self, ref, r0, x):
        for g in range(self.grp):
            ref[0, pl.ds(r0, BAND_SUB), g * self.d:(g + 1) * self.d] = \
                x[g * BAND_SUB:(g + 1) * BAND_SUB].astype(ref.dtype)

    def tables(self, r0):
        """(cos, sin) of the sub-block's positions for ``rows``."""
        cos_ref, sin_ref, _ = self.rotary
        return [jnp.concatenate(
            [ref[pl.ds(r0, BAND_SUB), :].astype(_F32)] * self.grp, axis=0)
            for ref in (cos_ref, sin_ref)]

    def queries(self, q_ref, r0, tables):
        """The sub-block's queries, rotated (``tables(r0)``) and
        scaled."""
        q = self.rows(q_ref, r0)
        if self.rotary:
            q = _rotated(q, *tables, self.rotary[2][...])
        return (q.astype(_F32) * self.scale).astype(q.dtype)

    def keys(self, k_ref):
        """The block of keys, rotated."""
        if not self.rotary:
            return k_ref[0]
        cos_ref, sin_ref, turn_ref = self.rotary
        return _rotated(k_ref[0], cos_ref[...].astype(_F32),
                        sin_ref[...].astype(_F32), turn_ref[...])

    def gates(self, r0):
        """The gates (sigmoid of the logits) of ``rows``' heads, a
        column."""
        return jnp.concatenate(
            [self.gate_ref[0, 0, pl.ds(r0, BAND_SUB), g:g + 1]
             for g in range(self.grp)], axis=0)


def _band_fwd_kernel(*refs, window, scale, bq, nq, grp, rotary, gated):
    # grid (batch, key/value head, query block); the group's query heads
    # go through a product together, as rows: the keys they share are
    # the matrix unit's stationary operand once for all of them
    *refs, o_ref, lse_ref, kb_ref, vb_ref = refs
    q_ref, k_ref, v_ref, *refs = refs
    rotary_refs = gate_ref = None
    if rotary:
        rotary_refs, refs = refs[:3], refs[3:]
    if gated:
        gate_ref, = refs
    i = pl.program_id(2)
    sb, d, n = BAND_SUB, k_ref.shape[-1], window + BAND_SUB
    group = _Group(grp, d, scale, rotary_refs, gate_ref)
    _next_block(i, bq, ((kb_ref, group.keys(k_ref)), (vb_ref, v_ref[0])))
    keeps = _edge_masks(grp, 1)

    def sub_block(r0, lo, first):
        q = group.queries(q_ref, r0, group.tables(r0) if rotary else None)
        s = _dot(q, kb_ref[pl.ds(lo, n)], _NT)            # (grp * sb, keys)
        s = _masked(s, 1, keeps, lo, bq, first)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        o = _dot(p.astype(vb_ref.dtype), vb_ref[pl.ds(lo, n)], _NN) / l
        if gated:
            o = o.astype(o_ref.dtype).astype(_F32) * group.gates(r0)
        group.put(o_ref, r0, o)
        # the row statistics are columns here; they leave as rows
        lse = jnp.broadcast_to(m + jnp.log(l), (grp * sb, 128)).T[:1]
        row = i * (bq // sb) + r0 // sb
        for g in range(grp):
            lse_ref[0, g, pl.ds(row, 1), :] = lse[:, g * sb:(g + 1) * sb]

    _sub_blocks(i, nq, bq, window, sub_block)


def _band_bwd_kernel(*refs, window, scale, bq, nq, grp, rotary, gated):
    # the forward's grid and one more query block: a key block has its
    # gradient whole once the query block after it has been through, so
    # step i writes dk and dv of block i - 1
    *refs, kb_ref, vb_ref, dkb_ref, dvb_ref = refs
    q_ref, k_ref, v_ref, do_ref, lse_ref, *refs = refs
    rotary_refs = gate_ref = None
    if gated:
        gate_rows_ref, *refs = refs
    if rotary:
        cos_ref, sin_ref, cos_before, sin_before, turn_ref, *refs = refs
        rotary_refs = (cos_ref, sin_ref, turn_ref)
    if gated:
        gate_ref, *refs = refs
    dq_ref, dk_ref, dv_ref, *dgate_ref = refs
    i = pl.program_id(2)
    sb, d, n = BAND_SUB, k_ref.shape[-1], window + BAND_SUB
    group = _Group(grp, d, scale, rotary_refs, gate_ref)
    zeros = jnp.zeros((bq, d), _F32)
    _next_block(i, bq, ((kb_ref, group.keys(k_ref)), (vb_ref, v_ref[0]),
                        (dkb_ref, zeros), (dvb_ref, zeros)))
    keeps = _edge_masks(grp, 0)

    def sub_block(r0, lo, first):
        # keys along the rows, the group's queries along the lanes: the
        # row statistics are rows, the sums over a group's heads happen
        # inside the products that make dk and dv, and of the five
        # products only dq wants an operand turned
        tables = group.tables(r0) if rotary else None
        q = group.queries(q_ref, r0, tables)
        do = group.rows(do_ref, r0)
        if gated:
            do = (do.astype(_F32) * group.gates(r0)).astype(do.dtype)
        row = jnp.minimum(i, nq - 1) * (bq // sb) + r0 // sb

        def stats(ref):
            return jnp.concatenate([ref[0, g, pl.ds(row, 1), :]
                                    for g in range(grp)], axis=1)

        kb = kb_ref[pl.ds(lo, n)]
        s = _dot(kb, q, _NT)                              # (keys, grp * sb)
        s = _masked(s, 0, keeps, lo, bq, first)
        p = jnp.exp(s - stats(lse_ref))
        dp = _dot(vb_ref[pl.ds(lo, n)], do, _NT)
        # the whole band of a query is here: the softmax's backward sum
        # from the same p and dp that make ds
        delta = jnp.sum(p * dp, axis=0, keepdims=True)
        ds = (p * (dp - delta)).astype(q.dtype)
        dvb_ref[pl.ds(lo, n)] += _dot(p.astype(q.dtype), do, _NN)
        # q carries the scale: ds^T @ (q * scale) is dk
        dkb_ref[pl.ds(lo, n)] += _dot(ds, q, _NN)
        dq = (scale * _dot(ds, kb, _TN)).astype(q.dtype)  # (grp * sb, D)
        if rotary:
            dq = _unrotated(dq, *tables, turn_ref[...])
        group.put(dq_ref, r0, dq)
        if gated:
            # with o' = o * g, g = sigmoid(z): sum_d do' o = delta / g,
            # so dz = delta * (1 - g), and o is not needed
            dz = delta * (1.0 - stats(gate_rows_ref))
            for g in range(grp):
                dgate_ref[0][0, g, pl.ds(row, 1), :] = \
                    dz[:, g * sb:(g + 1) * sb]

    _sub_blocks(i, nq, bq, window, sub_block)
    dk = dkb_ref[:bq].astype(dk_ref.dtype)
    if rotary:
        dk = _unrotated(dk, cos_before[...].astype(_F32),
                        sin_before[...].astype(_F32), turn_ref[...])
    dk_ref[0] = dk
    dv_ref[0] = dvb_ref[:bq].astype(dv_ref.dtype)


def _band_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=96 * 1024 * 1024)


def _band_call(kernel_fn, name, q, k, v, rotary, gates, more, outs, scratch,
               extra_steps, interpret, **static):
    """One of the two kernels over the grid (batch, key/value head,
    query block + ``extra_steps``). Operands: q, k, v as (B, T, H * D);
    ``more`` (kind, array) pairs after them; the rotary tables (at the
    step's block and, in the backward kernel, at the block before) and
    the gates a column a head where given. ``outs``: kinds."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    grp, block = hq // hkv, static["bq"]
    nq = t // block

    # a step past the last query block computes nothing and keeps the
    # blocks of the step before it: nothing is fetched or written twice
    def at(b_, h, i):
        return (b_, jnp.minimum(i, nq - 1), h)

    def before(b_, h, i):
        return (b_, jnp.maximum(i - 1, 0), h)

    specs = dict(
        q=pl.BlockSpec((1, block, grp * d), at),
        k=pl.BlockSpec((1, block, d), at),
        k_before=pl.BlockSpec((1, block, d), before),
        table=pl.BlockSpec((block, d), lambda b_, h, i: (at(b_, h, i)[1], 0)),
        table_before=pl.BlockSpec(
            (block, d), lambda b_, h, i: (before(b_, h, i)[1], 0)),
        turn=pl.BlockSpec((d, d), lambda b_, h, i: (0, 0)),
        # a group's row statistics (the logsumexp; the gates and their
        # logits' gradient): a sub-block's are one row of lanes; whole
        # while the grid walks the group's query blocks
        rows=pl.BlockSpec((1, grp, t // BAND_SUB, BAND_SUB),
                          lambda b_, h, i: (b_, h, 0, 0)),
        gate=pl.BlockSpec((1, 1, block, grp),
                          lambda b_, h, i: (b_, h, at(b_, h, i)[1], 0)))
    shapes = dict(
        q=jax.ShapeDtypeStruct((b, t, hq * d), q.dtype),
        k=jax.ShapeDtypeStruct((b, t, hkv * d), k.dtype),
        rows=jax.ShapeDtypeStruct((b, hq, t // BAND_SUB, BAND_SUB), _F32))
    ins = [("q", q.reshape(b, t, hq * d)), ("k", k.reshape(b, t, hkv * d)),
           ("k", v.reshape(b, t, hkv * d))] + more
    if rotary is not None:
        cos, sin, turn = [jnp.asarray(x, q.dtype) for x in rotary]
        tables = ("table", "table_before")[:1 + extra_steps]
        ins += [(kind, x) for kind in tables for x in (cos, sin)]
        ins.append(("turn", turn))
    if gates is not None:
        # (B, T, Hq) -> (B, Hkv, T, G)
        ins.append(("gate", gates.reshape(b, t, hkv, grp)
                    .transpose(0, 2, 1, 3)))
    return pl.pallas_call(
        functools.partial(kernel_fn, nq=nq, grp=grp, rotary=rotary is not None,
                          gated=gates is not None, **static),
        grid=(b, hkv, nq + extra_steps),
        in_specs=[specs[kind] for kind, _ in ins],
        out_specs=[specs[kind] for kind in outs],
        out_shape=[shapes[kind.split("_")[0]] for kind in outs],
        scratch_shapes=scratch, compiler_params=_band_params(interpret),
        interpret=interpret, name=name)(*[x for _, x in ins])


def _gates(gate):
    return None if gate is None else jax.nn.sigmoid(gate.astype(_F32))


def _band_fwd(q, k, v, rotary, gate, window, scale, block, interpret):
    """``q`` (B, T, Hq, D), ``k`` / ``v`` (B, T, Hkv, D) -> the result
    (B, T, Hq, D) and the logsumexp (B, Hq, T / BAND_SUB, BAND_SUB)
    float32. A key/value head is a column block of D in
    (B, T, Hkv * D), its group of query heads one of G * D in
    (B, T, Hq * D)."""
    d = q.shape[-1]
    o, lse = _band_call(
        _band_fwd_kernel, "band_attention_fwd", q, k, v, rotary,
        _gates(gate), [], ("q", "rows"),
        [pltpu.VMEM((2 * block, d), k.dtype),
         pltpu.VMEM((2 * block, d), v.dtype)], 0, interpret,
        window=window, scale=scale, bq=block)
    return o.reshape(q.shape), lse


def _band_bwd(q, k, v, rotary, gate, lse, do, window, scale, block,
              interpret):
    b, t, hq, d = q.shape
    gates = _gates(gate)
    more = [("q", do.reshape(b, t, hq * d)), ("rows", lse)]
    outs = ("q", "k_before", "k_before")
    if gate is not None:
        # (B, T, Hq) -> (B, Hq, T / BAND_SUB, BAND_SUB), and back
        more.append(("rows", gates.transpose(0, 2, 1).reshape(lse.shape)))
        outs += ("rows",)
    dq, dk, dv, *dgate = _band_call(
        _band_bwd_kernel, "band_attention_bwd", q, k, v, rotary, gates, more,
        outs, [pltpu.VMEM((2 * block, d), k.dtype),
               pltpu.VMEM((2 * block, d), v.dtype),
               pltpu.VMEM((2 * block, d), _F32),
               pltpu.VMEM((2 * block, d), _F32)], 1, interpret,
        window=window, scale=scale, bq=block)
    dgate = dgate[0].reshape(b, hq, t).transpose(0, 2, 1) \
        .astype(gate.dtype) if dgate else None
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dgate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _band_attention(q, k, v, rotary, gate, window, scale, block, interpret):
    return _band_fwd(q, k, v, rotary, gate, window, scale, block,
                     interpret)[0]


def _band_vjp_fwd(q, k, v, rotary, gate, window, scale, block, interpret):
    o, lse = _band_fwd(q, k, v, rotary, gate, window, scale, block,
                       interpret)
    return o, (q, k, v, rotary, gate, lse)


def _band_vjp_bwd(window, scale, block, interpret, res, do):
    q, k, v, rotary, gate, lse = res
    dq, dk, dv, dgate = _band_bwd(q, k, v, rotary, gate, lse, do, window,
                                  scale, block, interpret)
    # the rotary tables are constants of the position
    return dq, dk, dv, jax.tree.map(jnp.zeros_like, rotary), dgate


_band_attention.defvjp(_band_vjp_fwd, _band_vjp_bwd)


# ---------------------------------------------------------------------------

def _check(hq, hkv, window):
    if hq % hkv:
        raise ValueError("query heads must be a multiple of key/value "
                         f"heads, got {hq} and {hkv}")
    if window is not None and window < 1:
        raise ValueError(f"window must be at least 1, got {window}")


def _heads_major(x):
    """(B, T, H, D) <-> (B, H, T, D)."""
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "block", "scale",
                                             "backend"))
def banded_attention(q, k, v, window=None, block=None, scale=None,
                     backend=None):
    """Causal grouped-query attention, keys no further back than
    ``window - 1`` positions (no limit with ``window=None``); see the
    module docstring. ``backend``: ``"xla"``, ``"splash"``
    (``"splash_interpret"``: the kernel interpreted, for tests off the
    chip), ``"band"`` / ``"band_interpret"`` (this repo's band kernel,
    which wants the heads token-major: see
    ``banded_attention_token_major``) or None (the splash kernel on a
    TPU where the shapes allow it, else the composition).
    ``block``: rows of a query block (and of a key block); None takes
    the backend's own (``SPLASH_*_BLOCK``, ``XLA_BLOCK``,
    ``BAND_BLOCK``)."""
    _check(q.shape[1], k.shape[1], window)
    if backend in ("band", "band_interpret"):
        return _heads_major(banded_attention_token_major(
            _heads_major(q), _heads_major(k), _heads_major(v), window=window,
            block=block, scale=scale, backend=backend))
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    if backend is None:
        backend = default_backend(*q.shape[2:], dv=v.shape[-1])
    if backend in ("splash", "splash_interpret"):
        return _splash_attention(q, k, v, window, block, scale,
                                 interpret=backend == "splash_interpret")
    return _xla_attention(q, k, v, window, block or XLA_BLOCK, scale)


@functools.partial(jax.jit, static_argnames=("window", "block", "scale",
                                             "backend"))
def banded_attention_token_major(q, k, v, window=None, block=None,
                                 scale=None, backend=None, rotary=None,
                                 gate=None):
    """``banded_attention`` on heads as the projections leave them:
    ``q`` (B, T, Hq, D), ``k`` / ``v`` (B, T, Hkv, D) to (B, T, Hq, D).
    With ``backend`` None a window the band kernel takes
    (``band_available``) runs it on a TPU, with no transpose on either
    side; every other call transposes to ``banded_attention`` and back.

    The band kernel alone takes what a model does on either side of
    the products into the same pass: ``rotary = (cos, sin, turn)``
    (tables (T, D) and the signed permutation (D, D) of
    ``models.laguna.rotary_tables``) turns ``q`` and ``k`` by their
    positions first, ``x * cos + (x @ turn) * sin``; ``gate`` (B, T, Hq)
    multiplies each head's result by ``sigmoid(gate)``."""
    _check(q.shape[2], k.shape[2], window)
    t, d = q.shape[1], q.shape[3]
    if backend is None:
        backend = default_backend(t, d, window, q.shape[2] // k.shape[2])
    if backend not in ("band", "band_interpret"):
        if rotary is not None or gate is not None:
            raise ValueError("the band kernel alone takes the rotary "
                             f"positions and the gate, not {backend!r}")
        return _heads_major(banded_attention(
            _heads_major(q), _heads_major(k), _heads_major(v), window=window,
            block=block, scale=scale, backend=backend))
    bq = block or max(BAND_BLOCK, window or 0)
    if not _band_fits(t, d, window, bq):
        raise ValueError(
            f"the band kernel takes no window of {window} over {t} "
            f"tokens at head size {d} in blocks of {bq}")
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    return _band_attention(q, k, v, rotary, gate, window, scale, bq,
                           backend == "band_interpret")
