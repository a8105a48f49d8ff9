"""Pallas TPU kernels for the hot ops.

Attention as one kernel: the scores, the float32 softmax and the
weighted sum of a head, forward and backward, with no T x T array
written to HBM or kept between the passes.

- products: one bfloat16 pass of the matrix unit with float32
  accumulation, which is what XLA's default-precision dot does to
  float32 operands on a TPU; q, k, v, do and the probabilities are cast
  tile by tile inside the kernel, the statistics and the softmax stay
  float32, results leave in the operands' dtype.
- layout: the kernels take a head as (D, T), its rows along the
  lanes, and hold a block of scores as (keys, queries). A 64-wide head
  then fills whole registers and whole HBM tiles; the per-query
  statistics (maximum, sum, logsumexp, delta) are rows that broadcast
  down the sublanes; of the seven products only the two that make the
  scores want a (small) operand transposed, and no T x T array ever is.
  XLA keeps the projections' heads with T minor by itself, so inside a
  step the (B, H, T, D) -> (BH, D, T) transposes around the kernels are
  free; a row-major kernel costs three relayout copies forward and four
  backward (PERF.md section 6, PR 29).
- forward: where a head's keys fit one block (T <= DEFAULT_BLOCK_K) a
  block of queries meets them all at once; longer sequences stream K/V
  blocks through the innermost grid dimension under an online softmax.
  The logsumexp leaves as (BH, 1, T).
- backward: one kernel body recomputes a block of scores once. Keys in
  one block: dq, dk and dv from one sweep over the Q blocks; else two
  sweeps (dq over K blocks, dk/dv over Q blocks).

Falls back to the XLA composition (parallel/ring_attention
.local_attention) when a head size does not tile; in interpret mode the
products keep the operands' dtype, so the CPU tests compare with
XLA:CPU's float32 dense attention.
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
           "count_traced", "gspmd_partitioned"]

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
LANES = 128
MIN_SEQ = 384
VMEM_LIMIT = 48 * 1024 * 1024

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


def flash_attention_available(q_len: int, k_len: int, head_dim: int,
                              dtype=jnp.float32) -> bool:
    """True where the kernel is the faster way to these shapes: the one
    rule of its call sites, on what a call can see.

    The kernel pads and masks what does not tile (sequence lengths to
    whole lane widths, head_dim 96 -> 128), so what it cannot take is
    little: a head_dim past 256 that is no multiple of 128, a dtype the
    matrix unit does not multiply. Where it loses to the dense
    composition it is not offered either. By the chip's measurement at
    B x H = 192, forward plus backward, D 64 and 128, float32 and
    bfloat16 (``tools/attention_table.py``; PERF.md section 6, PR 29):
    dense wins up to T = 256, where a head's scores are small and a
    grid step a head is mostly its own overhead (0.08-0.59 ms against
    0.21-0.83); from MIN_SEQ up the kernel wins, 1.4-6.5 times. False
    while tracing for GSPMD partitioning (:func:`gspmd_partitioned`)."""
    if getattr(_scope, "partitioned", False):
        return False
    return (jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and (head_dim <= 256 or head_dim % 128 == 0)
            and min(q_len, k_len) >= MIN_SEQ)


def count_traced(backend: str):
    """One traced attention call that took ``backend`` (``kernel`` or
    ``dense``): telemetry counter ``attention_traced_total.<backend>``,
    bumped by the call sites that ask :func:`flash_attention_available`."""
    from ..telemetry import metrics
    metrics.counter(f"attention_traced_total.{backend}",
                    "attention calls traced, by backend").inc()


def _mxu_dtype(dtype, interpret):
    """What the products multiply in: bfloat16 on the chip, one pass of
    the matrix unit, which is what XLA's default-precision dot does to
    float32 operands there, so the kernel rounds what the dense
    composition rounds. In interpret mode the operands stay as they
    are, as in XLA:CPU's default-precision dot, which the tests compare
    with."""
    return dtype if interpret else jnp.bfloat16


def _dot(a, b, dims):
    """One pass of the matrix unit, float32 accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NN = ((1,), (0,))   # a @ b
_NT = ((1,), (1,))   # a @ b.T
_TN = ((0,), (0,))   # a.T @ b


def _masked(s, causal, kv_len, q0, k0):
    """Scores (keys along the rows, queries along the lanes, starting at
    positions ``k0`` and ``q0``) with the causal triangle and K's padded
    tail set to NEG_INF. Padding lives at the TAIL of K and a causal
    query always sees key 0, so a query's maximum is a real score and a
    masked exp(s - m) underflows to 0."""
    if not causal and kv_len is None:
        return s
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    keep = None if kv_len is None else k_pos < kv_len
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = q_pos >= k_pos if keep is None else keep & (q_pos >= k_pos)
    return jnp.where(keep, s, NEG_INF)


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


# ---------------------------------------------------------------------------
# forward: grid (BH, nq, nk). Where a head's keys fit one block (nk == 1)
# a block of queries meets them all at once: no loop, no running maximum.
# ---------------------------------------------------------------------------

def _scores(q_ref, k_ref, causal, kv_len, scale, q0, k0, mxu):
    """(q * scale, the block of scores (bk, bq) in float32)."""
    q = (q_ref[0].astype(jnp.float32) * scale).astype(mxu)       # (d, bq)
    s = _dot(k_ref[0].astype(mxu), q, _TN)                       # (bk, bq)
    return q, _masked(s, causal, kv_len, q0, k0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                causal, scale, bq, bk, nk, kv_len, mxu):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    def scores():
        return _scores(q_ref, k_ref, causal, kv_len, scale,
                       qi * bq, kj * bk, mxu)[1]

    def weighted(p):
        return _dot(v_ref[0].astype(mxu), p.astype(mxu), _NN)    # (d, bq)

    if nk == 1:
        s = scores()
        m = jnp.max(s, axis=0, keepdims=True)                    # (1, bq)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=0, keepdims=True)
        o_ref[0] = (weighted(p) / l).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l)
        return

    acc_ref, m_ref, l_ref = scratch

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: K blocks strictly above the diagonal contribute nothing
    @pl.when((qi + 1) * bq - 1 >= kj * bk if causal else True)
    def _step():
        s = scores()
        m_prev = m_ref[...]                                      # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + weighted(p)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_fwd(q, k, v, causal, s, bq, bk, interpret, kv_len=None):
    """q/k/v: (BH, D, T) -> (out (BH, D, Tq), lse (BH, 1, Tq) fp32)."""
    BH, D, Tq = q.shape
    Tk = k.shape[2]
    nq, nk = Tq // bq, Tk // bk
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=s,
                               bq=bq, bk=bk, nk=nk, kv_len=kv_len,
                               mxu=_mxu_dtype(q.dtype, interpret))
    scratch = [] if nk == 1 else [pltpu.VMEM((D, bq), jnp.float32),
                                  pltpu.VMEM((1, bq), jnp.float32),
                                  pltpu.VMEM((1, bq), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, D, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, D, bk), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, D, bk), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, D, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, D, Tq), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: one kernel body that recomputes a block of scores once. Where
# a head's keys fit one block it gives dq, dk and dv in one sweep over the
# Q blocks; longer sequences run it twice: dq sweeping K blocks, dk/dv
# sweeping Q blocks.
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, *refs,
                emit, causal, scale, bq, bk, kv_len, mxu):
    # grid (BH, outer, inner): the inner axis sweeps K blocks for
    # emit == "dq" and Q blocks otherwise ("dkv", "all")
    outer, inner = pl.program_id(1), pl.program_id(2)
    qi, kj = (outer, inner) if emit == "dq" else (inner, outer)
    last = pl.num_programs(2) - 1
    if emit == "dq":
        delta_ref, dq_ref, dq_acc = refs
    elif emit == "dkv":
        delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs

    @pl.when(inner == 0)
    def _init():
        if emit == "dq":
            dq_acc[...] = jnp.zeros_like(dq_acc)
        else:
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((qi + 1) * bq - 1 >= kj * bk if causal else True)
    def _step():
        q, s = _scores(q_ref, k_ref, causal, kv_len, scale,
                       qi * bq, kj * bk, mxu)                    # (bk, bq)
        do = do_ref[0].astype(mxu)                               # (d, bq)
        p = jnp.exp(s - lse_ref[0])                              # lse (1, bq)
        dp = _dot(v_ref[0].astype(mxu), do, _TN)                 # (bk, bq)
        if emit == "all":
            # every key is here: the softmax's backward sum from the
            # same p and dp that make ds, as the dense composition
            # takes it, so that a query's ds sums to zero to float32
            # (what keeps the key bias's gradient at rounding noise)
            delta = jnp.sum(p * dp, axis=0, keepdims=True)
        else:
            delta = delta_ref[0]
        ds = (p * (dp - delta)).astype(mxu)
        if emit != "dq":
            dv_acc[...] += _dot(do, p.astype(mxu), _NT)          # (d, bk)
            # q carries the scale: (q * scale) @ ds^T is dk
            dk_acc[...] += _dot(q, ds, _NT)
        if emit != "dkv":
            dq = _dot(k_ref[0].astype(mxu), ds, _NN)             # (d, bq)
            if emit == "dq":
                dq_acc[...] += dq
            else:
                dq_ref[0] = (scale * dq).astype(dq_ref.dtype)

    @pl.when(inner == last)
    def _flush():
        if emit == "dq":
            dq_ref[0] = (scale * dq_acc[...]).astype(dq_ref.dtype)
        else:
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, s, bq, bk, interpret,
               kv_len=None):
    """(BH, D, T) operands, lse (BH, 1, Tq) -> (dq, dk, dv) as
    (BH, D, T); no T x T array leaves the kernels."""
    BH, D, Tq = q.shape
    Tk = k.shape[2]
    nq, nk = Tq // bq, Tk // bk

    def call(emit, *rows):
        sweep_k = emit == "dq"
        grid = (BH, nq, nk) if sweep_k else (BH, nk, nq)

        def at(which):
            # block index of a Q-side or K-side array at (b, outer, inner)
            if (which == "q") == sweep_k:
                return lambda b, o, i: (b, 0, o)
            return lambda b, o, i: (b, 0, i)

        q_spec = pl.BlockSpec((1, D, bq), at("q"))
        k_spec = pl.BlockSpec((1, D, bk), at("k"))
        row_spec = pl.BlockSpec((1, 1, bq), at("q"))
        dq_out = (q_spec, jax.ShapeDtypeStruct((BH, D, Tq), q.dtype))
        dk_out = (k_spec, jax.ShapeDtypeStruct((BH, D, Tk), k.dtype))
        dv_out = (k_spec, jax.ShapeDtypeStruct((BH, D, Tk), v.dtype))
        outs = {"dq": [dq_out], "dkv": [dk_out, dv_out],
                "all": [dq_out, dk_out, dv_out]}[emit]
        acc = [pltpu.VMEM((D, bq), jnp.float32)] if sweep_k else \
            [pltpu.VMEM((D, bk), jnp.float32)] * 2
        return pl.pallas_call(
            functools.partial(_bwd_kernel, emit=emit, causal=causal,
                              scale=s, bq=bq, bk=bk, kv_len=kv_len,
                              mxu=_mxu_dtype(q.dtype, interpret)),
            grid=grid,
            in_specs=[q_spec, k_spec, k_spec, q_spec]
            + [row_spec] * len(rows),
            out_specs=[spec for spec, _ in outs],
            out_shape=[shape for _, shape in outs],
            scratch_shapes=acc,
            compiler_params=_compiler_params(interpret),
            interpret=interpret,
        )(q, k, v, g, *rows)

    if nk == 1:
        return call("all", lse)
    # keys in several blocks: the softmax's backward sum over all of
    # them is sum_d dO_id * O_id, rowwise; XLA fuses this
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=1, keepdims=True)                # (BH, 1, Tq)
    (dq,), (dk, dv) = call("dq", lse, delta), call("dkv", lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (B, H, T, D) with custom vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _fa_vjp_fwd(q, k, v, causal, scale, block_q, block_k,
                         interpret)
    return out


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """q/k/v: (B, H, T, D). Scores, softmax and weighted sum in one
    kernel, forward and backward. Jitted: an eager call is one program,
    and the layers of a step that call it at one shape share one
    lowering of the kernels."""
    return _flash_attention(q, k, v, causal, scale, block_q, block_k,
                            interpret)


def _round_up(n, m):
    return -(-n // m) * m


def _block(length, most):
    """The largest block of at most ``most`` rows that divides
    ``length``, in whole lane widths where ``most`` allows."""
    step = min(LANES, most)
    return max(b for b in range(step, most + 1, step) if length % b == 0)


def _plan_blocks(q, k, block_q, block_k):
    """Tiling plan, or None for the dense-XLA fallback.

    Sequences pad up to whole lane widths (the tail of K masked via
    kv_len) and take the largest blocks that divide them; head_dim
    96 -> 128 etc. (zero-padding the contraction is numerically exact;
    the padded output/grad columns are sliced off). BERT-shaped configs
    (T=384, D=96 per head after 12x64 splits, ...) must run the kernel,
    not silently fall back."""
    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    if (D > 256 and D % 128 != 0) or min(Tq, Tk) < LANES // 2:
        return None
    Tqp, Tkp = _round_up(Tq, LANES), _round_up(Tk, LANES)
    return dict(bq=_block(Tqp, block_q), bk=_block(Tkp, block_k),
                Tqp=Tqp, Tkp=Tkp,
                Dp=64 if D <= 64 else _round_up(D, 128))


def _heads_t(x, T, D):
    """(B, H, t, d) -> (B*H, D, T): a head's rows along the lanes, zero
    padded. Inside a program the transpose costs nothing where XLA keeps
    the producer's result with T minor, as it does for the projections'
    64-wide heads."""
    B, H, t, d = x.shape
    x = jnp.swapaxes(x.reshape(B * H, t, d), 1, 2)
    if t == T and d == D:
        return x
    return jnp.pad(x, ((0, 0), (0, D - d), (0, T - t)))


def _heads(x, B, t, d):
    """(B*H, D, T) -> (B, H, t, d): ``_heads_t`` back."""
    return jnp.swapaxes(x[:, :d, :t], 1, 2).reshape(B, -1, t, d)


def _fa_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    plan = _plan_blocks(q, k, block_q, block_k)
    if plan is None:
        from ..parallel.ring_attention import local_attention
        out = local_attention(q, k, v, scale=s, causal=causal)
        return out, (q, k, v, None, None)
    B, Tq, Tk, D = q.shape[0], q.shape[2], k.shape[2], q.shape[3]
    q3 = _heads_t(q, plan["Tqp"], plan["Dp"])
    k3 = _heads_t(k, plan["Tkp"], plan["Dp"])
    v3 = _heads_t(v, plan["Tkp"], plan["Dp"])
    kv_len = Tk if plan["Tkp"] != Tk else None
    out, lse = _flash_fwd(q3, k3, v3, causal, s, plan["bq"], plan["bk"],
                          interpret, kv_len=kv_len)
    out = _heads(out, B, Tq, D)
    return out, (q, k, v, out, lse)


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
    B, Tq, Tk, D = q.shape[0], q.shape[2], k.shape[2], q.shape[3]
    q3 = _heads_t(q, plan["Tqp"], plan["Dp"])
    k3 = _heads_t(k, plan["Tkp"], plan["Dp"])
    v3 = _heads_t(v, plan["Tkp"], plan["Dp"])
    g3 = _heads_t(g, plan["Tqp"], plan["Dp"])
    o3 = _heads_t(out, plan["Tqp"], plan["Dp"])   # read past one K block
    # the forward's lse holds the padded q rows too: they met real keys
    # with q = 0, and their do = 0 keeps them out of dk/dv (their dq is
    # sliced off)
    kv_len = Tk if plan["Tkp"] != Tk else None
    dq, dk, dv = _flash_bwd(q3, k3, v3, o3, lse, g3, causal, s,
                            plan["bq"], plan["bk"], interpret,
                            kv_len=kv_len)
    return (_heads(dq, B, Tq, D), _heads(dk, B, Tk, D),
            _heads(dv, B, Tk, D))


_flash_attention.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)
