"""The state-space duality (SSD) of a Mamba-2 mixer (Dao and Gu,
arXiv:2405.21060), as a chunked scan: on a TPU a Pallas kernel pair,
elsewhere XLA.

``ssd(x, dt, a, b, c)``: ``x`` (B, T, H, P), ``dt`` (B, T, H) a step
size a token and head (after the caller's softplus), ``a`` (H,) a
negative rate a head, ``b`` and ``c`` (B, T, G, N) shared by the
``H / G`` heads of a group (head ``h`` reads group ``h // (H / G)``).
Every head of every sequence keeps a state ``S`` (N, P), zero at the
sequence's start:

    S_t = exp(dt_t a) S_{t-1} + dt_t b_t x_t^T
    y_t = c_t^T S_t                                       (B, T, H, P)

(the skip ``D x`` is the caller's). ``ssd_packed`` is the same on x, B
and C side by side as a Mamba-2 mixer's filter leaves them, (B, T,
H * P + 2 G N), and gives y as (B, T, H * P).

Chunks of L tokens. With ``g`` the running sum of ``dt a`` inside a
chunk (float32), ``X`` the chunk's ``dt x`` and ``S`` the state that
enters it,

    Y     = (C B^T o L) X + e^g o (C S)
    L     = e^(g_t - g_s) for s <= t, 0 above
    S_out = e^(g_end) S + B^T (e^(g_end - g) o X)

``L`` is masked above its diagonal BEFORE the exponent, so no entry
leaves float32's range whatever the decay. Two implementations of it,
by ``default_backend`` (a shape rule, no flag; ``backend=`` forces one,
for tests and tables; a mixer counts what it traced under
``ssd_traced_total.kernel`` / ``.chunked``):

- the kernel pair (on a TPU where ``ssd_available``: a group's heads
  span whole lane widths, the state is whole lane widths) reads a
  group's heads of x as one (L, H / G * P) column block and its B and
  C as (L, N) column blocks of the packed (B, T, H * P + 2 G N), and
  writes y the same way, (B, T, H * P); grid (batch, group, chunk of
  ``SSD_KERNEL_CHUNK`` tokens), the chunks in order. A grid step builds
  ``g``, ``C B^T`` once for the group and each head's decays and scores
  in fast memory, and carries the group's states, float32, (N, H / G *
  P), in fast memory from chunk to chunk: neither decays nor scores
  reach HBM. The forward kernel that feeds the backward pass also
  writes the state entering each chunk; the backward kernel walks the
  chunks in reverse, carries the states' gradient the same way,
  rebuilds ``g``, ``C B^T`` and the decays, and writes the gradients of
  x, ``dt``, B and C (B's and C's summed over the group's heads) and a
  chunk's share of the rates'. Under a caller's ``jax.checkpoint`` the
  primal forward writes y alone (``optimize_remat``).
- the XLA path (``_chunked``; everywhere else, and the tests' oracle):
  chunks of ``SSD_CHUNK``, the products batched over heads x chunks
  (``C B^T`` once a group, the other three a head); one ``lax.scan``
  between the chunks carries the (H, N, P) float32 state. Its backward
  pass is autodiff's: it keeps the chunks' products and the states that
  enter them, so a caller that cannot afford a layer's residuals
  rematerialises the block that holds the call (the Mamba-2 mixer of
  ``models/nemotron_h.py`` is one rematerialised unit).

On both paths the operands of the products are ``x``'s dtype with
float32 accumulation (float32 operands at the highest precision); the
decays, ``g`` and the state are float32. T need not be a multiple of
the chunk: the tail is padded with tokens that change nothing (``dt``
zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import _NN, _NT, _TN, _dot, _exact

__all__ = ["ssd", "ssd_packed", "ssd_available", "default_backend",
           "count_traced", "SSD_CHUNK", "SSD_KERNEL_CHUNK"]

# Tokens a chunk of the XLA path. The published ``chunk_size`` is 128; at
# the Nemotron cell's mixer (8192 tokens, 64 heads of 64, 8 groups of a
# 128-wide state, bfloat16) on a TPU v5e, forward + backward under
# ``jax.checkpoint`` took 22.08 ms at 64, 12.86 at 128 and 10.07 at 256
# (``tools/attention_table.py --ssd 1``): the chunks' products fill the
# matrix unit better as they grow, the decays' passes grow with them.
SSD_CHUNK = 256
# Tokens a chunk of the kernel pair: 128 is the published chunk and one
# whole (128 x 128) tile of the matrix unit for ``C B^T`` and the scores.
SSD_KERNEL_CHUNK = 128

_F32 = jnp.float32


def _mm(eq, a, b, dtype):
    """``einsum(eq, a, b)`` with the operands in ``dtype`` and float32
    accumulation; float32 operands at the highest precision."""
    exact = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype), precision=exact,
                      preferred_element_type=jnp.float32)


def _chunked(x, dt, a, b, c, chunk):
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    groups = b.shape[2]
    r = h // groups
    dtype = x.dtype
    n = -(-t // chunk)

    def chunks(arr):
        """(B, T, ..) -> (B, n, chunk, ..), the tail padded with zeros."""
        arr = jnp.pad(arr, [(0, 0), (0, n * chunk - t)]
                      + [(0, 0)] * (arr.ndim - 2))
        return arr.reshape((bsz, n, chunk) + arr.shape[2:])

    def by_head(arr):
        """(B, n, chunk, H, ..) -> (B, n, G, H / G, chunk, ..)."""
        arr = arr.reshape((bsz, n, chunk, groups, r) + arr.shape[4:])
        return jnp.moveaxis(arr, 2, 4)

    dt = chunks(dt.astype(f32))
    g = by_head(jnp.cumsum(dt * a.astype(f32), axis=2))     # (B,n,G,R,L)
    xdt = by_head(chunks(x.astype(f32)) * dt[..., None])    # (B,n,G,R,L,P)
    bg = jnp.moveaxis(chunks(b), 2, 3)                      # (B,n,G,L,N)
    cg = jnp.moveaxis(chunks(c), 2, 3)
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(col <= row, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                    # (B,n,G,R,L,L)
    scores = _mm("bcgln,bcgsn->bcgls", cg, bg, dtype)[:, :, :, None] * decay
    y = _mm("bcgrls,bcgrsp->bcgrlp", scores, xdt, dtype)
    g_end = g[..., -1:]
    states = _mm("bcgsn,bcgrsp->bcgrnp", bg,
                 xdt * jnp.exp(g_end - g)[..., None], dtype)

    def turn(state, x):
        contribution, chunk_decay = x
        return state * chunk_decay[..., None, None] + contribution, state

    _, entering = jax.lax.scan(
        turn, jnp.zeros((bsz, groups, r) + states.shape[-2:], f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(g_end[..., 0]), 1, 0)))
    y = y + _mm("bcgln,bcgrnp->bcgrlp", cg, jnp.moveaxis(entering, 0, 1),
                dtype) * jnp.exp(g)[..., None]
    y = jnp.moveaxis(y, 4, 2).reshape(bsz, n * chunk, h, p)
    return y[:, :t].astype(dtype)


# ---------------------------------------------------------------------------
# The kernel pair: a (batch, group, chunk) a grid step, the group's states
# in fast memory from chunk to chunk.


def ssd_available(heads, head_dim, groups, state) -> bool:
    """The kernel takes any length (the tail is padded to whole chunks)
    where a group's heads span whole lane widths and the state is whole
    lane widths, so that x, B and C are column blocks of the packed
    (B, T, H * P + 2 G N)."""
    return heads % groups == 0 and heads // groups * head_dim % 128 == 0 \
        and state % 128 == 0 and heads * head_dim % state == 0


def default_backend(heads, head_dim, groups, state) -> str:
    """``"kernel"`` on a TPU where ``ssd_available``, else ``"chunked"``
    (the XLA scan above)."""
    if jax.default_backend() == "tpu" \
            and ssd_available(heads, head_dim, groups, state):
        return "kernel"
    return "chunked"


class _Chunk:
    """What a grid step computes from a chunk before the state, for one
    group of ``R`` heads of ``P``: ``g`` (L, R) and its transpose; over
    the heads' lanes (L, R P) ``e^g``, ``e^(g_end - g)``, ``e^(g_end)``
    and ``X = dt x``, float32; ``C B^T`` (L, L); ``head(h)``: head
    ``h``'s decays (L, L), masked above the diagonal before the
    exponent. A head's values go to its lanes by lane broadcasts and come
    back by lane sums, float32 throughout."""

    def __init__(self, x, b, c, dt, a, head_dim, dtype):
        chunk, heads = dt.shape
        iota = jax.lax.broadcasted_iota
        row, col = iota(jnp.int32, (chunk, chunk), 0), \
            iota(jnp.int32, (chunk, chunk), 1)
        self.lower, self.upper = col <= row, col >= row
        self.lanes = iota(jnp.int32, (1, heads), 1)
        self.lane128 = iota(jnp.int32, (1, 128), 1)
        self.head_dim = head_dim
        self.dt = dt
        self.g = _exact(dt * a, self.lower)                 # (L, R)
        self.g_rows = self.g.T                              # (R, L)
        self.g_end = self.g[chunk - 1:chunk]                # (1, R)
        self.x = x.astype(_F32)
        # the decays' factors over the heads' lanes: e^g, e^(g_end - g),
        # e^(g_end)
        g, end = self.by_lane(self.g), self.by_lane(self.g_end)
        self.eg, self.w, self.decay = jnp.exp(g), jnp.exp(end - g), \
            jnp.exp(end)
        self.dt_lanes = self.by_lane(dt)
        self.xdt = self.x * self.dt_lanes
        self.b, self.c = b, c
        self.cb = _dot(c, b, _NT, dtype)

    def by_lane(self, v):
        """(.., R) -> (.., R P): each head's value over its lanes."""
        spans = []
        for start in range(0, self.g.shape[1] * self.head_dim, 128):
            first = start // self.head_dim
            out = jnp.broadcast_to(v[:, first:first + 1], (v.shape[0], 128))
            for h in range(first + 1, -(-(start + 128) // self.head_dim)):
                out = jnp.where(self.lane128 + start >= h * self.head_dim,
                                v[:, h:h + 1], out)
            spans.append(out)
        return jnp.concatenate(spans, axis=1)

    def head_sum(self, v):
        """(.., R P) -> (.., R): the sum over each head's lanes."""
        out = 0.0
        for h in range(self.g.shape[1]):
            total = 0.0
            for start, stop, mask in self.spans(h):
                total = total + jnp.sum(jnp.where(mask, v[:, start:stop], 0.0),
                                        axis=1, keepdims=True)
            out = out + jnp.where(self.lanes == h, total, 0.0)
        return out

    def head(self, h):
        gap = self.g[:, h:h + 1] - self.g_rows[h:h + 1, :]
        return jnp.exp(jnp.where(self.lower, gap, -jnp.inf))

    def spans(self, h):
        """Head ``h``'s lanes as (start, stop, mask) of the 128-lane spans
        they cover: the mask (1, 128) where a span's lane is the head's."""
        p, lane = self.head_dim, self.lane128
        for start in range(h * p // 128 * 128, (h + 1) * p, 128):
            yield start, start + 128, (lane + start >= h * p) \
                & (lane + start < (h + 1) * p)


def _per_head(ch, products):
    """Sum over the group's heads of ``products(h, start, stop)`` (L, 128)
    on each 128-lane span of head ``h``, each kept on the head's own
    lanes: (L, R P). ``products`` is called head by head, in order."""
    width = ch.x.shape[1]
    spans = [0.0] * (width // 128)
    for h in range(ch.g.shape[1]):
        for start, stop, mask in ch.spans(h):
            spans[start // 128] = spans[start // 128] + jnp.where(
                mask, products(h, start, stop), 0.0)
    return jnp.concatenate(spans, axis=1)


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, *refs, head_dim,
                keep):
    """One chunk of one group: the state that enters it read from fast
    memory, y written, the state that leaves it put back; with ``keep``
    the entering state written out for the backward kernel."""
    states_ref, state_ref = refs if keep else (None, refs[0])
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = jnp.zeros_like(state_ref)

    ch = _Chunk(x_ref[0], b_ref[0], c_ref[0], dt_ref[0, 0].T, a_ref[0],
                head_dim, dtype)
    state = state_ref[...]                                  # (N, R P)
    if keep:
        states_ref[0, 0, 0] = state
    y = ch.eg * _dot(ch.c, state, _NN, dtype) + _per_head(
        ch, lambda h, i, j: _dot(ch.cb * ch.head(h), ch.xdt[:, i:j], _NN,
                                 dtype))
    state_ref[...] = ch.decay * state + _dot(ch.b, ch.w * ch.xdt, _TN,
                                             dtype)
    y_ref[0] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, states_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dstate_ref, *,
                head_dim):
    """One chunk of one group, the grid walking the chunks backwards: the
    chunk's algebra again from the state saved at its entry, the
    gradient of the state that leaves it read from fast memory and that
    of the state that enters it put back."""
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    ch = _Chunk(x_ref[0], b_ref[0], c_ref[0], dt_ref[0, 0].T, a_ref[0],
                head_dim, dtype)
    heads = ch.g.shape[1]
    state = states_ref[0, 0, 0]                             # (N, R P)
    dstate = dstate_ref[...]                                # leaving
    dy = dy_ref[0].astype(_F32)
    # y += e^g o (C S)
    ey = ch.eg * dy
    dc = _dot(ey, state, _NT, dtype)
    dstate_ref[...] = ch.decay * dstate + _dot(ch.c, ey, _TN, dtype)
    # S_out = e^(g_end) S + B^T (w o X)
    b_ds = _dot(ch.b, dstate, _NN, dtype)                   # (L, R P)
    wx = ch.w * ch.xdt
    db = _dot(wx, dstate, _NT, dtype)
    dxdt = ch.w * b_ds
    # e^g's and e^(g_end - g)'s gradients, and e^(g_end)'s
    dw = wx * b_ds
    dg = ch.head_sum(ey * _dot(ch.c, state, _NN, dtype) - dw)  # (L, R)
    dg_end = ch.head_sum(jnp.sum(dw, axis=0, keepdims=True) + ch.decay
                         * jnp.sum(state * dstate, axis=0, keepdims=True))
    # Y += (C B^T o L) X, a head at a time
    dcb = 0.0
    dg_rows = jnp.zeros_like(ch.g_rows)
    scores = []
    heads_rows = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
    for h in range(heads):
        decays = ch.head(h)
        score = ch.cb * decays
        dscore = 0.0
        for start, stop, mask in ch.spans(h):
            dscore = dscore + _dot(jnp.where(mask, dy[:, start:stop], 0.0),
                                   ch.xdt[:, start:stop], _NT, dtype)
        pairs = dscore * score
        dg = dg + jnp.where(ch.lanes == h,
                            jnp.sum(pairs, axis=1, keepdims=True), 0.0)
        dg_rows = dg_rows - jnp.where(heads_rows == h, jnp.sum(
            pairs, axis=0, keepdims=True), 0.0)
        dcb = dcb + dscore * decays
        scores.append(score)
    dxdt = dxdt + _per_head(
        ch, lambda h, i, j: _dot(scores[h], dy[:, i:j], _TN, dtype))
    dc = dc + _dot(dcb, ch.b, _NN, dtype)
    db = db + _dot(dcb, ch.c, _TN, dtype)
    # g is the running sum of dt a: a step's gradient is every later one's
    dda = _exact(dg + dg_rows.T, ch.upper) + dg_end
    dx_ref[0] = (ch.dt_lanes * dxdt).astype(dx_ref.dtype)
    db_ref[0] = db.astype(db_ref.dtype)
    dc_ref[0] = dc.astype(dc_ref.dtype)
    ddt_ref[0, 0] = (a_ref[0] * dda + ch.head_sum(ch.x * dxdt)).T
    da_ref[0, 0, 0] = jnp.sum(ch.dt * dda, axis=0, keepdims=True)


def _specs(heads, head_dim, groups, state, chunk, n, backwards):
    """Block specs over the grid (batch, group, chunk): a group's x (L,
    R P) and its B and C (L, N), column blocks of the packed (B, T, H P +
    2 G N); y and its gradient, x's gradient (B, T, H P) and B's and C's
    (B, T, G N) the same blocks; ``dt`` (B, G, R, T); ``a`` (G, 1, R);
    the states entering the chunks (B, G, n, N, R P) and the rates'
    gradient a chunk (B, G, n, 1, R)."""
    width = heads // groups * head_dim

    def at(i):
        return n - 1 - i if backwards else i

    return dict(
        x=pl.BlockSpec((1, chunk, width), lambda b_, g_, i: (b_, at(i), g_)),
        b=pl.BlockSpec((1, chunk, state), lambda b_, g_, i: (
            b_, at(i), heads * head_dim // state + g_)),
        c=pl.BlockSpec((1, chunk, state), lambda b_, g_, i: (
            b_, at(i), heads * head_dim // state + groups + g_)),
        bc=pl.BlockSpec((1, chunk, state), lambda b_, g_, i: (b_, at(i), g_)),
        dt=pl.BlockSpec((1, 1, heads // groups, chunk),
                        lambda b_, g_, i: (b_, g_, 0, at(i))),
        a=pl.BlockSpec((1, 1, heads // groups), lambda b_, g_, i: (g_, 0, 0)),
        states=pl.BlockSpec((1, 1, 1, state, width),
                            lambda b_, g_, i: (b_, g_, at(i), 0, 0)),
        da=pl.BlockSpec((1, 1, 1, 1, heads // groups),
                        lambda b_, g_, i: (b_, g_, at(i), 0, 0)))


def _kernel_fwd(xbc, dt, a, shape, chunk, interpret, keep):
    """``xbc`` (B, T, H P + 2 G N), ``dt`` (B, G, R, T), ``a`` (G, 1, R),
    T whole chunks -> y (B, T, H P) and, with ``keep``, the states
    entering the chunks."""
    heads, head_dim, groups, state = shape
    b, t, _ = xbc.shape
    n = t // chunk
    width = heads // groups * head_dim
    specs = _specs(*shape, chunk, n, False)
    out_specs = [specs["x"]]
    out_shape = [jax.ShapeDtypeStruct((b, t, heads * head_dim), xbc.dtype)]
    if keep:
        out_specs.append(specs["states"])
        out_shape.append(jax.ShapeDtypeStruct((b, groups, n, state, width),
                                              _F32))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=head_dim, keep=keep),
        grid=(b, groups, n),
        in_specs=[specs["x"], specs["b"], specs["c"], specs["dt"],
                  specs["a"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((state, width), _F32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="ssd_fwd")(xbc, xbc, xbc, dt, a)
    return outs if keep else outs[0]


def _kernel_bwd(xbc, dt, a, states, dy, shape, chunk, interpret):
    heads, head_dim, groups, state = shape
    b, t, _ = xbc.shape
    n = t // chunk
    width = heads // groups * head_dim
    specs = _specs(*shape, chunk, n, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=head_dim),
        grid=(b, groups, n),
        in_specs=[specs["x"], specs["b"], specs["c"], specs["dt"],
                  specs["a"], specs["states"], specs["x"]],
        out_specs=[specs["x"], specs["bc"], specs["bc"], specs["dt"],
                   specs["da"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, heads * head_dim), xbc.dtype),
            jax.ShapeDtypeStruct((b, t, groups * state), xbc.dtype),
            jax.ShapeDtypeStruct((b, t, groups * state), xbc.dtype),
            jax.ShapeDtypeStruct(dt.shape, _F32),
            jax.ShapeDtypeStruct((b, groups, n, 1, heads // groups), _F32)],
        scratch_shapes=[pltpu.VMEM((state, width), _F32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="ssd_bwd")(xbc, xbc, xbc, dt, a, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel(xbc, dt, a, shape, chunk, interpret):
    return _kernel_fwd(xbc, dt, a, shape, chunk, interpret, False)


def _kernel_vjp_fwd(xbc, dt, a, shape, chunk, interpret):
    y, states = _kernel_fwd(xbc, dt, a, shape, chunk, interpret, True)
    return y, (xbc, dt, a, states)


def _kernel_vjp_bwd(shape, chunk, interpret, res, dy):
    xbc, dt, a, states = res
    dx, db, dc, ddt, da = _kernel_bwd(xbc, dt, a, states, dy, shape, chunk,
                                      interpret)
    return (jnp.concatenate([dx, db, dc], axis=-1), ddt,
            jnp.sum(da, axis=(0, 2)))


_kernel.defvjp(_kernel_vjp_fwd, _kernel_vjp_bwd, optimize_remat=True)


def _kernel_path(xbc, dt, a, groups, state, chunk, interpret):
    """The kernel pair on the packed ``xbc`` (B, T, H P + 2 G N): the
    tail padded to whole chunks with tokens that change nothing, ``dt``
    as (B, G, H / G, T), ``a`` as (G, 1, H / G) -> y (B, T, H P)."""
    b, t, width = xbc.shape
    heads = dt.shape[-1]
    shape = (heads, (width - 2 * groups * state) // heads, groups, state)
    tail = -t % chunk
    if tail:
        xbc = jnp.pad(xbc, ((0, 0), (0, tail), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, tail), (0, 0)))
    dt = dt.astype(_F32).reshape(b, t + tail, groups, -1).transpose(0, 2, 3, 1)
    y = _kernel(xbc, dt, a.astype(_F32).reshape(groups, 1, -1), shape, chunk,
                interpret)
    return y[:, :t]


def _unpack(xbc, heads, groups, state):
    """x (B, T, H, P), B and C (B, T, G, N) of the packed ``xbc``."""
    b, t, width = xbc.shape
    inner = width - 2 * groups * state
    bc = xbc[..., inner:].reshape(b, t, 2, groups, state)
    return (xbc[..., :inner].reshape(b, t, heads, -1), bc[:, :, 0],
            bc[:, :, 1])


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def _ssd(x, dt, a, b, c, chunk, backend):
    if backend == "chunked":
        return _chunked(x, dt, a, b, c, chunk or SSD_CHUNK)
    bsz, t, h, p = x.shape
    xbc = jnp.concatenate([x.reshape(bsz, t, h * p)] + [
        v.reshape(bsz, t, -1).astype(x.dtype) for v in (b, c)], axis=-1)
    return ssd_packed(xbc, dt, a, b.shape[2], b.shape[3], backend,
                      chunk).reshape(x.shape)


def _check(x, dt, a, b, c):
    bsz, t, h, _ = x.shape if x.ndim == 4 else (None,) * 4
    if x.ndim != 4 or dt.shape != (bsz, t, h) or a.shape != (h,) \
            or b.ndim != 4 or b.shape != c.shape or b.shape[:2] != (bsz, t) \
            or h % b.shape[2]:
        raise ValueError(
            "ssd: x (B, T, H, P), dt (B, T, H), a (H,), b and c (B, T, G, N) "
            f"with G dividing H; got {x.shape}, {dt.shape}, {a.shape}, "
            f"{b.shape}, {c.shape}")


def ssd(x, dt, a, b, c, chunk=None, backend=None):
    """The SSD recurrence (module docstring): ``x`` (B, T, H, P), ``dt``
    (B, T, H), ``a`` (H,), ``b``, ``c`` (B, T, G, N) with G dividing H ->
    ``y`` (B, T, H, P) in ``x``'s dtype, without the skip. ``chunk``:
    tokens a chunk (``SSD_CHUNK`` on the XLA path, ``SSD_KERNEL_CHUNK``
    on the kernel's). ``backend``: ``"chunked"`` (the XLA scan),
    ``"kernel"`` (the Pallas pair; ``"kernel_interpret"`` interprets it,
    for tests off the chip) or None (``default_backend``)."""
    _check(x, dt, a, b, c)
    if backend is None:
        backend = default_backend(x.shape[2], x.shape[3], *b.shape[2:])
    return _ssd(x, dt, a, b, c, chunk=chunk, backend=backend)


@functools.partial(jax.jit, static_argnames=("groups", "state", "backend",
                                             "chunk"))
def ssd_packed(xbc, dt, a, groups, state, backend, chunk=None):
    """The SSD (module docstring) of x, B and C side by side as a Mamba-2
    mixer's filter leaves them: ``xbc`` (B, T, H P + 2 G N), ``dt``
    (B, T, H), ``a`` (H,) -> y (B, T, H P) in ``xbc``'s dtype. The kernel
    pair reads x, B and C where they lie. ``backend`` as ``ssd``'s, named
    (the caller asked ``default_backend`` and counted it); ``chunk`` as
    ``ssd``'s."""
    if backend == "chunked":
        x, b, c = _unpack(xbc, dt.shape[-1], groups, state)
        return _chunked(x, dt, a, b, c, chunk or SSD_CHUNK).reshape(
            xbc.shape[:2] + (-1,))
    if backend not in ("kernel", "kernel_interpret"):
        raise ValueError(f"ssd: no backend {backend!r}")
    return _kernel_path(xbc, dt, a, groups, state, chunk or SSD_KERNEL_CHUNK,
                        backend == "kernel_interpret")


def count_traced(backend="chunked"):
    """One traced mixer that took ``backend``: telemetry counter
    ``ssd_traced_total.kernel`` (the Pallas pair, interpreted or not) or
    ``.chunked`` (the XLA scan)."""
    from ..telemetry import metrics
    label = "kernel" if backend.startswith("kernel") else "chunked"
    metrics.counter(f"ssd_traced_total.{label}",
                    "state-space scans traced, by backend").inc()
