"""Pallas TPU kernels for the hot ops.

Flash attention: the kernel the reference era hand-wrote in CUDA for
attention-adjacent workloads is here a Pallas kernel tiled for the MXU.
Memory is O(T) in sequence length on both passes:

- forward: K/V blocks stream through VMEM via the innermost grid
  dimension (double-buffered by Mosaic), online softmax in fp32
  accumulators held in VMEM scratch across the K sweep; the row
  logsumexp is emitted as a second output for the backward.
- backward: two tiled kernels with per-block recompute of the
  probabilities from (q, k, lse) — dq sweeps K blocks, dk/dv sweeps Q
  blocks — never materializing a T x T matrix (the flash-attention
  backward; round-1 used a dense jax.vjp here, which was O(T^2)).

Falls back to the XLA composition (parallel/ring_attention
.local_attention) on CPU or when shapes don't tile — same numerics, so
tests validate the kernels in interpret mode.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_available",
           "gspmd_partitioned"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30

_scope = threading.local()


@contextlib.contextmanager
def gspmd_partitioned():
    """Scope for tracing a program that GSPMD will partition over more
    than one device (shard/stepfn.py). Mosaic kernels cannot be
    partitioned automatically — lowering refuses them outside a
    shard_map — so inside this scope :func:`flash_attention_available`
    is False and callers trace the XLA composition, which GSPMD can
    shard."""
    was = getattr(_scope, "partitioned", False)
    _scope.partitioned = True
    try:
        yield
    finally:
        _scope.partitioned = was


def flash_attention_available(q_len: int, k_len: int, head_dim: int) -> bool:
    """True when the tiled kernel path handles these shapes.

    The kernels pad/mask internally (sequence lengths to the block
    size, head_dim 96 -> 128, etc.: BERT shapes must not silently fall
    back), so the only hard requirement is a head_dim the MXU can tile
    after padding. Very short sequences still fall back: padding 16
    tokens to a 128 block would waste >8x the FLOPs of the dense
    composition. False while tracing for GSPMD partitioning
    (:func:`gspmd_partitioned`)."""
    if getattr(_scope, "partitioned", False):
        return False
    return ((head_dim <= 256 or head_dim % 128 == 0)
            and min(q_len, k_len) >= DEFAULT_BLOCK_Q // 2)


def _dot32(a, b, trans_a=False, trans_b=False):
    """MXU matmul with fp32 accumulation regardless of input dtype."""
    dn = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dn,
                               preferred_element_type=jnp.float32)


def _causal_mask(s, qi, bq, kj, bk):
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _kv_mask(s, kj, bk, kv_len):
    """Mask K positions beyond the un-padded length. Padding lives at
    the TAIL of K, so a valid row always sees a real value before any
    fully-masked block — its running max stays real and the masked
    exp(s - m) underflows to 0 instead of the degenerate exp(0)."""
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos < kv_len, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward: grid (BH, nq, nk) — K/V stream through the innermost dimension
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, causal, scale, bq, bk, nk,
                kv_len=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: K blocks strictly above the diagonal contribute nothing
    needed = (qi + 1) * bq - 1 >= kj * bk if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = _dot32(q, k, trans_b=True)                  # (bq, bk)
        if causal:
            s = _causal_mask(s, qi, bq, kj, bk)
        if kv_len is not None:
            s = _kv_mask(s, kj, bk, kv_len)
        m_prev = m_ref[:, 0:1]                          # (bq, 1)
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                          # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot32(p, v)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _flush():
        l = l_ref[:, 0:1]
        m = m_ref[:, 0:1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(jnp.maximum(l, 1e-20))   # (bq, 1)


def _flash_fwd(q, k, v, causal, s, bq, bk, interpret, kv_len=None):
    """q/k/v: (BH, T, D) -> (out (BH, Tq, D), lse (BH, Tq) fp32)."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // bq, Tk // bk
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=s,
                               bq=bq, bk=bk, nk=nk, kv_len=kv_len)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # (BH, Tq, 1): the last-two-dims of every block must be
            # (8, 128)-aligned or span the array — a (1, bq) row block
            # is rejected by the Mosaic lowering
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dq sweeps K blocks; dk/dv sweeps Q blocks (per-block recompute)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, causal, scale, bq, bk, nk, kv_len=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = (qi + 1) * bq - 1 >= kj * bk if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                 # (bq, 1)
        delta = delta_ref[0]
        s = _dot32(q, k, trans_b=True)
        if causal:
            s = _causal_mask(s, qi, bq, kj, bk)
        if kv_len is not None:
            s = _kv_mask(s, kj, bk, kv_len)
        p = jnp.exp(s - lse)                             # (bq, bk)
        dp = _dot32(do, v, trans_b=True)                 # (bq, bk)
        ds = p * (dp - delta)
        acc_ref[...] += scale * _dot32(ds, k)            # (bq, d)

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    causal, scale, bq, bk, nq, kv_len=None):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = (qi + 1) * bq - 1 >= kj * bk if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                 # (bq, 1)
        delta = delta_ref[0]
        s = _dot32(q, k, trans_b=True)                   # (bq, bk)
        if causal:
            s = _causal_mask(s, qi, bq, kj, bk)
        if kv_len is not None:
            s = _kv_mask(s, kj, bk, kv_len)
        p = jnp.exp(s - lse)
        dv_acc[...] += _dot32(p, do, trans_a=True)       # (bk, d)
        dp = _dot32(do, v, trans_b=True)
        ds = p * (dp - delta)                            # (bq, bk)
        # scale * ds^T @ (q*scale)/scale = scale * ds^T @ q_raw
        dk_acc[...] += _dot32(ds, q, trans_a=True)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, s, bq, bk, interpret,
               kv_len=None):
    """(BH, T, D) operands -> (dq, dk, dv), O(T) memory."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // bq, Tk // bk
    # delta_i = sum_d dO_id * O_id — rowwise, XLA fuses this
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)               # (BH, Tq, 1)
    row_spec_q = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=s,
                          bq=bq, bk=bk, nk=nk, kv_len=kv_len),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            row_spec_q,
            row_spec_q,
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    row_spec_kq = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=s,
                          bq=bq, bk=bk, nq=nq, kv_len=kv_len),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            row_spec_kq,
            row_spec_kq,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (B, H, T, D) with custom vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """q/k/v: (B, H, T, D). Tiled online-softmax attention on the MXU."""
    out, _ = _fa_vjp_fwd(q, k, v, causal, scale, block_q, block_k,
                         interpret)
    return out


def _round_up(n, m):
    return -(-n // m) * m


def _plan_blocks(q, k, block_q, block_k):
    """Tiling plan, or None for the dense-XLA fallback.

    Exact-tiling shapes keep the round-3 behavior (block clamped to the
    sequence, no padding). Everything else pads: sequences up to block
    multiples (the tail K blocks masked via kv_len), head_dim 96 -> 128
    etc. (zero-padding the contraction is numerically exact; the padded
    output/grad columns are sliced off). BERT-shaped configs (T=384,
    D=96 per head after 12x64 splits, ...) must run the kernel, not
    silently fall back."""
    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if Tq % bq == 0 and Tk % bk == 0 and (D % 128 == 0
                                          or D in (64, 128, 256)):
        return dict(bq=bq, bk=bk, Tqp=Tq, Tkp=Tk, Dp=D, pad=False)
    if ((D > 256 and D % 128 != 0)
            or min(Tq, Tk) < DEFAULT_BLOCK_Q // 2):
        return None
    bq, bk = block_q, block_k
    return dict(bq=bq, bk=bk, Tqp=_round_up(Tq, bq),
                Tkp=_round_up(Tk, bk),
                Dp=64 if D <= 64 else _round_up(D, 128), pad=True)


def _pad3(x, T, D, value=0.0):
    """Zero-pad (BH, t, d) up to (BH, T, D)."""
    if x.shape[1] == T and x.shape[2] == D:
        return x
    return jnp.pad(x, ((0, 0), (0, T - x.shape[1]), (0, D - x.shape[2])),
                   constant_values=value)


def _fa_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    plan = _plan_blocks(q, k, block_q, block_k)
    if plan is None:
        from ..parallel.ring_attention import local_attention
        out = local_attention(q, k, v, scale=s, causal=causal)
        return out, (q, k, v, None, None)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q3 = _pad3(q.reshape(B * H, Tq, D), plan["Tqp"], plan["Dp"])
    k3 = _pad3(k.reshape(B * H, Tk, D), plan["Tkp"], plan["Dp"])
    v3 = _pad3(v.reshape(B * H, Tk, D), plan["Tkp"], plan["Dp"])
    kv_len = Tk if plan["Tkp"] != Tk else None
    out, lse = _flash_fwd(q3, k3, v3, causal, s, plan["bq"], plan["bk"],
                          interpret, kv_len=kv_len)
    out = out[:, :Tq, :D]
    lse = lse[:, :Tq]
    return out.reshape(B, H, Tq, D), (q, k, v, out, lse)


def _fa_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if lse is None:  # non-tiling fallback path: dense recompute vjp
        from ..parallel.ring_attention import local_attention

        def ref_attn(q_, k_, v_):
            return local_attention(q_, k_, v_, scale=s, causal=causal)

        _, vjp = jax.vjp(ref_attn, q, k, v)
        return vjp(g)
    plan = _plan_blocks(q, k, block_q, block_k)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q3 = _pad3(q.reshape(B * H, Tq, D), plan["Tqp"], plan["Dp"])
    k3 = _pad3(k.reshape(B * H, Tk, D), plan["Tkp"], plan["Dp"])
    v3 = _pad3(v.reshape(B * H, Tk, D), plan["Tkp"], plan["Dp"])
    o3 = _pad3(out, plan["Tqp"], plan["Dp"])
    g3 = _pad3(g.reshape(B * H, Tq, D), plan["Tqp"], plan["Dp"])
    # padded q rows: a large-positive lse drives their recomputed
    # p = exp(s - lse) to zero (their dq is sliced off anyway, and
    # ds = 0 keeps them out of dk/dv)
    lse3 = jnp.pad(lse, ((0, 0), (0, plan["Tqp"] - Tq), (0, 0)),
                   constant_values=1e5) if lse.shape[1] != plan["Tqp"] \
        else lse
    kv_len = Tk if plan["Tkp"] != Tk else None
    dq, dk, dv = _flash_bwd(q3, k3, v3, o3, lse3, g3, causal, s,
                            plan["bq"], plan["bk"], interpret,
                            kv_len=kv_len)
    return (dq[:, :Tq, :D].reshape(B, H, Tq, D),
            dk[:, :Tk, :D].reshape(B, H, Tk, D),
            dv[:, :Tk, :D].reshape(B, H, Tk, D))


flash_attention.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)
