"""Fused multi-layer RNN op (vanilla/LSTM/GRU).

TPU-native replacement for the reference fused RNN kernels
(ref: src/operator/rnn.cc + rnn-inl.h (1,635 LoC) + rnn_impl.h (2,364 LoC)
— CPU reference impl + cuDNN path). Here one `lax.scan` per layer: XLA
compiles the recurrence with the gate matmuls on the MXU; the packed
parameter layout (per layer per direction: W_i2h, W_h2h then b_i2h, b_h2h,
cuDNN gate order i,f,g,o for LSTM / r,z,n for GRU) is kept bit-compatible
with the reference so checkpoints port.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _layer_param_sizes(mode, input_size, H, bidirectional):
    g = _gates(mode)
    ndir = 2 if bidirectional else 1
    sizes = []
    for d in range(ndir):
        sizes.append(("wi", (g * H, input_size)))
        sizes.append(("wh", (g * H, H)))
    return sizes


def unpack_rnn_params(params, mode, num_layers, input_size, H, bidirectional):
    """Split the flat parameter vector into per-layer weight/bias arrays
    (matches rnn-inl.h GetParamSize layout: all weights first, then all
    biases)."""
    g = _gates(mode)
    ndir = 2 if bidirectional else 1
    weights = []
    offset = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else H * ndir
        layer_w = []
        for d in range(ndir):
            wi = params[offset:offset + g * H * in_sz].reshape(g * H, in_sz)
            offset += g * H * in_sz
            wh = params[offset:offset + g * H * H].reshape(g * H, H)
            offset += g * H * H
            layer_w.append((wi, wh))
        weights.append(layer_w)
    biases = []
    for layer in range(num_layers):
        layer_b = []
        for d in range(ndir):
            bi = params[offset:offset + g * H]
            offset += g * H
            bh = params[offset:offset + g * H]
            offset += g * H
            layer_b.append((bi, bh))
        biases.append(layer_b)
    return weights, biases


def rnn_param_size(mode, num_layers, input_size, H, bidirectional):
    g = _gates(mode)
    ndir = 2 if bidirectional else 1
    total = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else H * ndir
        total += ndir * (g * H * in_sz + g * H * H + 2 * g * H)
    return total


def _cell_step(mode, H):
    if mode == "lstm":
        def step(carry, gin):
            h, c = carry
            i, f, g_, o = jnp.split(gin, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            g_ = jnp.tanh(g_)
            o = jax.nn.sigmoid(o)
            c_new = f * c + i * g_
            h_new = o * jnp.tanh(c_new)
            return (h_new, c_new)
        return step
    if mode == "gru":
        def step(carry, parts):
            h = carry[0]
            gin_x, (wh, bh) = parts
            gh = jnp.matmul(h, wh.T) + bh
            rx, zx, nx = jnp.split(gin_x, 3, axis=-1)
            rh, zh, nh = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(rx + rh)
            z = jax.nn.sigmoid(zx + zh)
            n = jnp.tanh(nx + r * nh)
            h_new = (1 - z) * n + z * h
            return (h_new,)
        return step
    act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh

    def step(carry, gin):
        return (act(gin),)
    return step


def _layer_step(mode, wh, bh, H):
    """One timestep: (carry, pre-mixed input gates) -> (carry, y)."""
    if mode == "lstm":
        cell = _cell_step(mode, H)

        def step(carry, gx):
            h, c = carry
            gin = gx + jnp.matmul(h, wh.T)
            h2, c2 = cell((h, c), gin)
            return (h2, c2), h2
        return step
    if mode == "gru":
        def step(carry, gx):
            (h,) = carry
            gh = jnp.matmul(h, wh.T) + bh
            rx, zx, nx = jnp.split(gx, 3, axis=-1)
            rh, zh, nh = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(rx + rh)
            z = jax.nn.sigmoid(zx + zh)
            n = jnp.tanh(nx + r * nh)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
        return step
    act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh

    def step(carry, gx):
        (h,) = carry
        h2 = act(gx + jnp.matmul(h, wh.T))
        return (h2,), h2
    return step


# unrolling is only offered below this sequence length: past it the
# unrolled program's compile time dwarfs any steady-state win
_RNN_UNROLL_MAX_T = 32


def _run_layer(x, h0, c0, wi, wh, bi, bh, mode, reverse=False):
    """x: (T, B, I). Returns (outputs (T,B,H), h_T, c_T).

    The time loop has two equivalent lowerings — `lax.scan` (one
    compiled body, XLA while-loop; compiles fast, steady overhead per
    step) and full unrolling (T inlined bodies; slower compile, lets
    XLA fuse/pipeline across steps — often faster for short T). The
    winner is measured-and-cached per (mode, T, B, H) signature by
    operator_tune, the same machinery that picks a convolution's layout
    (ref role: operator_tune.h's measured-cost corpus tuning)."""
    H = wh.shape[1]
    gin_x = jnp.einsum("tbi,gi->tbg", x, wi) + bi + (
        0.0 if mode == "gru" else bh)
    init = (h0, c0) if mode == "lstm" else (h0,)
    step = _layer_step(mode, wh, bh, H)

    def run_scan(gin):
        carry, ys = jax.lax.scan(step, init, gin, reverse=reverse)
        return ys, carry

    def run_unroll(gin):
        T = gin.shape[0]
        order = range(T - 1, -1, -1) if reverse else range(T)
        carry = init
        ys = [None] * T
        for t in order:
            carry, ys[t] = step(carry, gin[t])
        return jnp.stack(ys), carry

    T = gin_x.shape[0]
    candidates = [("scan", run_scan)]
    if T <= _RNN_UNROLL_MAX_T:
        candidates.append(("unroll", run_unroll))
    from .. import operator_tune as _otune
    _, fn = _otune.choose(
        f"rnn_{mode}", candidates, gin_x,
        key=f"rnn_{mode}|T{T}|B{gin_x.shape[1]}|H{H}")
    ys, carry = fn(gin_x)
    if mode == "lstm":
        return ys, carry[0], carry[1]
    return ys, carry[0], None


def _rnn_visible(params):
    """1 output normally; with state_outputs also h_out (and c_out for
    LSTM) — ref: rnn-inl.h NumVisibleOutputs."""
    from .registry import parse_bool_param
    if not parse_bool_param(params.get("state_outputs", False)):
        return 1
    return 3 if params.get("mode", "lstm") == "lstm" else 2


@register_op("RNN", n_out=3, needs_rng=True, needs_train=True,
             input_names=("data", "parameters", "state", "state_cell"),
             visible_outputs=_rnn_visible)
def rnn(data, parameters, state, *rest, state_size=0, num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False,
        use_sequence_length=False, _training=False):
    """data: (T, B, I); state: (num_layers*ndir, B, H); for LSTM a second
    state input (cell) follows. Returns (output, h_out, c_out)."""
    raw_key = rest[-1] if rest else None
    state_cell = rest[0] if mode == "lstm" else None
    T, B, I = data.shape
    H = state_size
    ndir = 2 if bidirectional else 1
    weights, biases = unpack_rnn_params(parameters, mode, num_layers, I, H,
                                        bidirectional)
    x = data
    h_outs, c_outs = [], []
    for layer in range(num_layers):
        layer_outs = []
        for d in range(ndir):
            idx = layer * ndir + d
            h0 = state[idx]
            c0 = state_cell[idx] if mode == "lstm" else None
            wi, wh = weights[layer][d]
            bi, bh = biases[layer][d]
            ys, hT, cT = _run_layer(x, h0, c0, wi, wh, bi, bh, mode,
                                    reverse=(d == 1))
            layer_outs.append(ys)
            h_outs.append(hT)
            if mode == "lstm":
                c_outs.append(cT)
        x = layer_outs[0] if ndir == 1 else jnp.concatenate(layer_outs,
                                                            axis=-1)
        if p > 0 and _training and layer < num_layers - 1 \
                and raw_key is not None:
            key = jax.random.fold_in(jax.random.wrap_key_data(raw_key), layer)
            mask = jax.random.bernoulli(key, 1 - p, x.shape).astype(x.dtype)
            x = x * mask / (1 - p)
    h_out = jnp.stack(h_outs)
    c_out = jnp.stack(c_outs) if mode == "lstm" else jnp.zeros_like(h_out)
    return x, h_out, c_out
