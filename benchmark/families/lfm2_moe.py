"""Family ``lfm2_moe``: the LFM2-MoE decoder (``LiquidAI/LFM2-8B-A1B``,
``model_type: lfm2_moe``) as ``mxnet_tpu.models.Lfm2MoeLM`` builds it,
trained on a per-position cross-entropy, on ONE chip's share of a stated
deployment (the configuration's ``deployment``: which of the routed
experts and which slice of the vocabulary are held here).

Found by the family's name: the weights (one jitted call from the seed),
the program side (the Gluon net holding them, its loss, its batches),
the plain reference in straightforward ``jax.numpy`` (it imports nothing
of ``mxnet_tpu``), the matrix work a step needs from the shapes at two
FLOPs a multiply-add, and what the per-layer readers of this family's
cell compute from (``attention_products``, ``expert_products``,
``conv_block_work``).

The layer equations (the configuration's ``assumed`` lists what the
source leaves open). Layer *i*: ``h = x + op_i(norm(x))``,
``out = h + ffn_i(norm(h))``; RMSNorm in float32 with a weight, eps
``norm_eps``; one RMSNorm after the last layer; logits ``h E^T`` with
``E`` the embedding matrix (tied); the loss is the mean cross-entropy
over every position of a sequence, one number a sequence. No bias
anywhere.

- ``op_i`` where ``layer_types[i]`` is ``conv``: ``u = x W_in``
  (C -> 3C); ``(B, C, X)`` the three thirds of ``u`` in that order;
  ``z = B * X``; ``c[t] = sum_j w[:, j] z[t - 2 + j]`` over the
  ``conv_L_cache`` = 3 taps, ``z`` zero before a sequence's start;
  ``y = (C * c) W_out``.
- ``op_i`` where it is ``full_attention``: ``num_attention_heads`` query
  heads of ``hidden_size / num_attention_heads`` over
  ``num_key_value_heads`` key/value heads (query head *h* reads
  key/value head ``h // group``); q and k each through an RMSNorm over
  a head's dimensions (one weight for q, one for k); rotary positions
  over the whole head (rotate-half, ``rope_theta``); causal
  ``softmax(q k^T / sqrt(d)) v`` in float32; ``W_o``.
- ``ffn_i`` for ``i < num_dense_layers``: ``down(silu(gate x) * up x)``
  of ``intermediate_size``.
- ``ffn_i`` after: ``s = sigmoid(x W_r)`` over all the published experts
  in float32; the ``num_experts_per_tok`` experts with the largest
  ``s + b`` (``b`` the expert bias: float32, no gradient, no update; the
  lower index wins a tie); weights ``s_e / (sum of the chosen s +
  1e-6)`` times ``routed_scaling_factor``; the experts held here (the
  same gated FFN, ``moe_intermediate_size``) add their weighted
  outputs, experts held elsewhere add nothing. No shared expert.

Parameter names are the net's attribute paths
(``layers.2.moe.w_gate``). The experts' matrices are stacked
``(held, in, out)``; every other matrix is ``(out, in)``; a filter is
``(C, 3)``. The net also answers to ``head.weight``: a second name of
``embed.weight``, the one tied ``Parameter``. ``make_weights`` gives it
as the same array, and ``is_state`` says it is no leaf of its own, so
that the harness, which asks the net for its names, finds every one of
them among the weights, and the reference trains ``embed.weight`` alone.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.seeding import seed_key

INIT_STD = 0.02
# the selection bias has no update rule in the source and ships as
# zeros; here it is drawn at this scale, at which it changes the chosen
# experts of a quarter to a third of the tokens at the published widths
# (``assumed.expert_bias``; 0.03 changed a half on the chip)
BIAS_STD = 0.015
ROUTER_EPS = 1e-6
REF_QUERY_BLOCK = 1024  # queries a block in the reference's attention
TIED = "head.weight"    # the embedding's second name in the net


# ---------------------------------------------------------------------------
# the layers, from the sizes alone
# ---------------------------------------------------------------------------

def layer_plan(sizes):
    """One dict a layer: its index, whether its mixer is the short
    convolution and whether its FFN is routed."""
    return [{"i": i, "conv": sizes["layer_types"][i] == "conv",
             "sparse": i >= sizes["num_dense_layers"]}
            for i in range(sizes["num_hidden_layers"])]


def head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def experts_held(sizes):
    """``(first id, number held, number the router scores)``."""
    dep = sizes["deployment"]
    start, stop = dep["experts_held"]
    if stop - start != sizes["num_experts"]:
        raise ValueError("num_experts must count the experts held")
    return start, stop - start, dep["num_experts_published"]


def param_shapes(sizes):
    """name -> (shape, kind) for every leaf; kind ``matrix`` (bfloat16
    under the policy), ``router`` (float32), ``bias`` (the selection
    bias: float32, never trained) or ``ones`` (an RMSNorm's weight,
    float32)."""
    c, v, d = sizes["hidden_size"], sizes["vocab_size"], head_dim(sizes)
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    _, held, routed = experts_held(sizes)
    shapes = {"embed.weight": ((v, c), "matrix"),
              "norm.weight": ((c,), "ones")}
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}"
        shapes[f"{pre}.operator_norm.weight"] = ((c,), "ones")
        shapes[f"{pre}.ffn_norm.weight"] = ((c,), "ones")
        if layer["conv"]:
            shapes[f"{pre}.conv.in_proj.weight"] = ((3 * c, c), "matrix")
            shapes[f"{pre}.conv.filter"] = ((c, sizes["conv_L_cache"]),
                                            "matrix")
            shapes[f"{pre}.conv.out_proj.weight"] = ((c, c), "matrix")
        else:
            for name, cout, cin in (("q_proj", h * d, c),
                                    ("k_proj", kv * d, c),
                                    ("v_proj", kv * d, c),
                                    ("o_proj", c, h * d)):
                shapes[f"{pre}.attn.{name}.weight"] = ((cout, cin),
                                                       "matrix")
            shapes[f"{pre}.attn.q_norm.weight"] = ((d,), "ones")
            shapes[f"{pre}.attn.k_norm.weight"] = ((d,), "ones")
        if layer["sparse"]:
            shapes[f"{pre}.moe.router_weight"] = ((routed, c), "router")
            shapes[f"{pre}.moe.expert_bias"] = ((routed,), "bias")
            shapes[f"{pre}.moe.w_gate"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_up"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_down"] = ((held, fe, c), "matrix")
        else:
            shapes[f"{pre}.mlp.gate_proj.weight"] = ((f, c), "matrix")
            shapes[f"{pre}.mlp.up_proj.weight"] = ((f, c), "matrix")
            shapes[f"{pre}.mlp.down_proj.weight"] = ((c, f), "matrix")
    return shapes


def param_dtype(name, policy):
    if policy == "f32":
        return jnp.float32
    if policy != "bf16_norm_router_f32":
        raise ValueError(f"lfm2_moe: unknown dtype policy {policy!r}")
    if name.endswith(("norm.weight", "router_weight", "expert_bias")):
        return jnp.float32
    return jnp.bfloat16


def is_state(name):
    """Names among the weights that are no trained leaf: the selection
    bias (held fixed through a step) and the embedding's second name."""
    return name.endswith("expert_bias") or name == TIED


def _act_bytes(sizes):
    return 4 if sizes["dtype_policy"] == "f32" else 2


def attention_products(sizes, traffic, windowed):
    """``(flops, bytes)`` a training step needs for the two attention
    products (scores, weighted values) of the full layers: FLOPs at two
    a multiply-add over the causal half, forward once and backward
    twice; bytes the least HBM traffic in the activations' bytes: q, k,
    v and the result once each pass. The family has no windowed layer:
    nothing for ``windowed`` true."""
    if windowed:
        return 0.0, 0.0
    b, t, d = traffic["batch"], traffic["seq"], head_dim(sizes)
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layers = sum(1 for layer in layer_plan(sizes) if not layer["conv"])
    return (float(layers * 3 * 2 * 2 * b * h * (t * (t + 1) // 2) * d),
            float(layers * 3 * _act_bytes(sizes) * b * t * d
                  * (2 * h + 2 * kv)))


def expert_products(sizes, rows):
    """``(flops, bytes)`` a training step needs for one expert layer's
    three grouped products (gate, up, down) over ``rows`` routed rows:
    forward once and backward twice; bytes: the rows in and out of each
    product and the held experts' matrices once a pass."""
    c, fe = sizes["hidden_size"], sizes["moe_intermediate_size"]
    _, held, _ = experts_held(sizes)
    flops = 3 * 3 * 2 * rows * c * fe
    nbytes = 3 * _act_bytes(sizes) * (3 * rows * (c + fe)
                                      + 3 * held * c * fe)
    return float(flops), float(nbytes)


def expected_rows(sizes, traffic):
    """Rows an expert layer here gets a step under uniform routing."""
    _, held, routed = experts_held(sizes)
    return traffic["batch"] * traffic["seq"] \
        * sizes["num_experts_per_tok"] * held / routed


def conv_block_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the short-convolution
    mixers taken whole, each as ONE unit: its two products (``in_proj``,
    ``out_proj``) forward once and backward twice; bytes: the mixer's
    input, its output, the filter and the two matrices once a pass.
    Whatever the compiler fuses inside the mixer, it cannot do less."""
    c, taps = sizes["hidden_size"], sizes["conv_L_cache"]
    tok = traffic["batch"] * traffic["seq"]
    layers = sum(1 for layer in layer_plan(sizes) if layer["conv"])
    flops = 3 * 2 * tok * (3 * c * c + c * c)
    nbytes = 3 * _act_bytes(sizes) * (2 * tok * c + c * taps + 4 * c * c)
    return float(layers * flops), float(layers * nbytes)


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add,
    forward once and backward twice: per token the projections, the
    dense FFN, the router and the head; per sequence the two attention
    products over the causal half; per routed row the three grouped
    products, at the rows uniform routing sends here. Bytes as in
    ``bert.matrix_layers``, in the activations' bytes. Nothing is
    counted for recomputation, for the short convolution's filter, for
    rotary, norms, softmax, sorting, gathering or the update."""
    c, v, d = sizes["hidden_size"], sizes["vocab_size"], head_dim(sizes)
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    tok, nbytes = traffic["batch"] * traffic["seq"], _act_bytes(sizes)
    _, _, routed = experts_held(sizes)
    full = sum(1 for layer in layer_plan(sizes) if not layer["conv"])

    def product(name, cin, cout):
        return (name, float(3 * 2 * tok * cin * cout),
                float(3 * nbytes * (tok * (cin + cout) + cin * cout)))

    out = []
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}"
        if layer["conv"]:
            out.append(product(f"{pre}.conv.in_proj", c, 3 * c))
            out.append(product(f"{pre}.conv.out_proj", c, c))
        else:
            flops, moved = attention_products(sizes, traffic, False)
            out.append(product(f"{pre}.attn.qkv", c, (h + 2 * kv) * d))
            out.append((f"{pre}.attn.products", flops / full, moved / full))
            out.append(product(f"{pre}.attn.o_proj", h * d, c))
        if layer["sparse"]:
            out.append(product(f"{pre}.moe.router", c, routed))
            out.append((f"{pre}.moe.experts",)
                       + expert_products(sizes,
                                         expected_rows(sizes, traffic)))
        else:
            f = sizes["intermediate_size"]
            out += [product(f"{pre}.mlp.gate_proj", c, f),
                    product(f"{pre}.mlp.up_proj", c, f),
                    product(f"{pre}.mlp.down_proj", f, c)]
    out.append(product("head", c, v))
    return out


def needed_flops(sizes, traffic):
    """FLOPs one training step needs: the sum over ``matrix_layers``."""
    return sum(fl for _, fl, _ in matrix_layers(sizes, traffic))


def work_units(sizes, traffic):
    return {"tokens": traffic["batch"] * traffic["seq"]}


# ---------------------------------------------------------------------------
# weights and batches, on the device from the seed
# ---------------------------------------------------------------------------

def make_weights(sizes, policy, seed):
    """All leaves in one jitted call, in the type they are trained in:
    every matrix, filter and router normal with std 0.02, the selection
    bias normal with std ``BIAS_STD``, RMSNorm weights one; and the
    embedding again under its second name (the same array)."""
    shapes = param_shapes(sizes)

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            dt = param_dtype(name, policy)
            if kind == "ones":
                leaves[name] = jnp.ones(shape, dt)
            else:
                std = BIAS_STD if kind == "bias" else INIT_STD
                leaves[name] = (std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dt)
        return leaves

    leaves = jax.jit(build)(seed_key(seed, 0))
    leaves[TIED] = leaves["embed.weight"]
    return leaves


def make_batches(sizes, policy, traffic, seed):
    """``n_batches`` pairs of token ids and labels, uniform over the
    slice of the vocabulary held, every row its own draw, in one jitted
    call."""
    n, b, t = traffic["n_batches"], traffic["batch"], traffic["seq"]
    if t > sizes["max_position_embeddings"]:
        raise ValueError("the traffic's sequences are longer than the "
                         "configuration's positions")

    def build(key):
        kx, ky = jax.random.split(key)
        x = jax.random.randint(kx, (n, b, t), 0, sizes["vocab_size"])
        y = jax.random.randint(ky, (n, b, t), 0, sizes["vocab_size"])
        return x.astype(jnp.int32), y.astype(jnp.float32)

    xs, ys = jax.jit(build)(seed_key(seed, 1))
    return [(xs[i], ys[i]) for i in range(n)]


# ---------------------------------------------------------------------------
# the program side (the system under test)
# ---------------------------------------------------------------------------

# layer -> the expert ids (N, k) the program's routers chose in the
# set-up forward of the newest ``build_program``
PROGRAM_EXPERT_IDS = {}


def build_program(sizes, policy, weights, ctx, sample_x):
    """``models.Lfm2MoeLM.from_config`` on ``ctx`` holding ``weights``,
    and its loss. One untimed eager forward on the first batch follows:
    that is where the expert layers fill their telemetry gauges (rows
    routed here, the fullest expert over the mean, the share of tokens
    whose choice the bias changed)."""
    from mxnet_tpu import autograd, gluon, models
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = models.Lfm2MoeLM.from_config(sizes)
    net.initialize(ctx=ctx)
    params = net._collect_params_with_prefix()
    if set(params) != set(weights):
        raise RuntimeError("the net's parameters and the benchmark's "
                           "differ: " + str(sorted(set(params)
                                                   ^ set(weights))[:6]))
    if params[TIED] is not params["embed.weight"]:
        raise RuntimeError("the net's head is not tied to its embedding")
    for name, p in params.items():
        if name == TIED:
            continue
        dt = str(jnp.dtype(param_dtype(name, policy)))
        if str(p.data().dtype) != dt:
            p.cast(dt)
        # a copy: the fused step donates what the net holds
        p.set_data(_wrap(jnp.array(weights[name], copy=True)))
    with autograd.pause():
        net(_wrap(sample_x)).wait_to_read()
    PROGRAM_EXPERT_IDS.clear()
    for i, layer in enumerate(net.layers):
        if getattr(layer, "moe", None) is not None:
            PROGRAM_EXPERT_IDS[f"layers.{i}"] = layer.moe.last_expert_ids
    # (B, T, V) logits against (B, T) labels: the mean over a sequence's
    # positions, one loss a sequence
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def _rope(x, theta):
    """``x`` (B, T, H, D) with every head turned by its position:
    rotate-half over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(qh, kh, vh, q):
    """Dense causal attention, one head and one block of queries at a
    time. ``qh`` (B, H, T, D); ``kh`` / ``vh`` (B, Hkv, T, D)."""
    b, h, t, d = qh.shape
    if b == 0:  # the fault that leaves half of a batch of one out
        return jnp.zeros_like(qh)
    hkv = kh.shape[1]
    group = h // hkv
    bq = REF_QUERY_BLOCK if t % REF_QUERY_BLOCK == 0 else t
    nq = t // bq
    blocks = qh.reshape(b * h * nq, bq, d)
    kf, vf = kh.reshape(-1, t, d), vh.reshape(-1, t, d)
    kpos = jnp.arange(t)[None, :]

    def one(args):
        block, item = args
        head, start = item // nq, (item % nq) * bq
        kv = (head // h) * hkv + (head % h) // group
        s = q.out(q.inp(block) @ q.inp(kf[kv]).T) / math.sqrt(d)
        seen = kpos <= start + jnp.arange(bq)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return q.out(q.inp(p) @ q.inp(vf[kv]))

    out = jax.lax.map(jax.checkpoint(one),
                      (blocks, jnp.arange(b * h * nq)))
    return out.reshape(b, h, t, d)


def _short_conv(u, w):
    """``C * conv(B * X)``: the taps as shifted adds, zeros before a
    sequence's start. ``u`` (B, T, 3C); ``w`` (C, taps)."""
    t, taps = u.shape[1], w.shape[1]
    b, c, x = jnp.split(u, 3, axis=-1)
    z = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    return c * sum(w[:, j] * z[:, j:j + t] for j in range(taps))


def _gated_ffn(x, gate, up, down, q):
    """``gate`` / ``up`` (in, out), ``down`` (out, in) as (F, C)."""
    hidden = jax.nn.silu(q.out(q.inp(x) @ q.inp(gate))) \
        * q.out(q.inp(x) @ q.inp(up))
    return q.out(q.inp(q.act(hidden)) @ q.inp(down))


def routing(sizes, x, router_w, bias):
    """``(weights, expert ids)``, each (N, k), of the tokens ``x`` in
    float32: sigmoid scores over all the router's outputs, the k with
    the largest score plus bias, the unbiased scores of those
    renormalised with the epsilon, times the scaling factor."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32).T,
                     precision="highest")
    scores = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32),
                             sizes["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    return top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTER_EPS) \
        * sizes["routed_scaling_factor"], top_i


def _experts(sizes, x, p, q, held=None):
    """The held experts' part of the routed FFN for tokens ``x``
    (N, C): a loop over the experts, each on every token under a mask of
    its routing weight; no sort, no grouping. ``held``: (first id,
    count), the configuration's by default."""
    start, count = held or experts_held(sizes)[:2]
    weights, ids = routing(sizes, x, p["router_weight"],
                           jax.lax.stop_gradient(p["expert_bias"]))

    def one(acc, e):
        w = jnp.sum(jnp.where(ids == start + e, weights, 0.0), axis=-1)
        y = _gated_ffn(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], q)
        return acc + w[:, None].astype(x.dtype) * y, None

    acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          jnp.arange(count))
    return acc, ids


def _layer(sizes, layer, h, p, q):
    """``(the layer's output, the expert ids its router chose (N, k) or
    None)``."""
    b, t, c = h.shape
    eps = sizes["norm_eps"]

    def dense(x, name):
        return q.out(q.inp(x) @ q.inp(p[f"{name}.weight"]).T)

    x = q.act(_rms(h, p["operator_norm.weight"], eps))
    if layer["conv"]:
        mixed = _short_conv(dense(x, "conv.in_proj"), p["conv.filter"])
        h = h + dense(q.act(mixed), "conv.out_proj")
    else:
        d, heads = head_dim(sizes), sizes["num_attention_heads"]
        kv, theta = sizes["num_key_value_heads"], sizes["rope_theta"]
        qh = _rms(dense(x, "attn.q_proj").reshape(b, t, heads, d),
                  p["attn.q_norm.weight"], eps)
        kh = _rms(dense(x, "attn.k_proj").reshape(b, t, kv, d),
                  p["attn.k_norm.weight"], eps)
        vh = dense(x, "attn.v_proj").reshape(b, t, kv, d)
        o = _attention(q.act(_rope(qh, theta)).transpose(0, 2, 1, 3),
                       q.act(_rope(kh, theta)).transpose(0, 2, 1, 3),
                       q.act(vh).transpose(0, 2, 1, 3), q)
        h = h + dense(q.act(o.transpose(0, 2, 1, 3).reshape(b, t, c)),
                      "attn.o_proj")

    x = q.act(_rms(h, p["ffn_norm.weight"], eps))
    if not layer["sparse"]:
        return h + _gated_ffn(x, p["mlp.gate_proj.weight"].T,
                              p["mlp.up_proj.weight"].T,
                              p["mlp.down_proj.weight"].T, q), None
    own = {k[len("moe."):]: v for k, v in p.items() if k.startswith("moe.")}
    routed, ids = _experts(sizes, x.reshape(b * t, c), own, q)
    return h + routed.reshape(b, t, c), ids


def reference_logits(sizes, params, x, q, expert_ids=None):
    """Float32 logits (B, T, V) of the network on token ids ``x``, the
    head the embedding matrix itself; ``expert_ids``, a dict, is filled
    with each sparse layer's chosen expert ids under the layer's name
    (``layers.2``)."""
    embedding = params["embed.weight"]
    h = q.act(embedding[x])
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}."
        own = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        # one layer's activations at a time are kept for backward
        h, ids = jax.checkpoint(
            lambda h, own, layer=layer: _layer(sizes, layer, h, own, q))(
                h, own)
        if expert_ids is not None and ids is not None:
            expert_ids[pre[:-1]] = ids
    h = q.act(_rms(h, params["norm.weight"], sizes["norm_eps"]))
    return q.out(q.inp(h) @ q.inp(embedding).T).astype(jnp.float32)


def reference_loss(sizes, params, x, y, q, key):
    """Per-sequence mean cross-entropy of the network, and no state.
    ``params`` hold every leaf in the dtype to compute in; ``q.inp`` is
    called on every operand of a matrix product, ``q.out`` on its result
    and ``q.act`` on every array kept between products
    (``correctness.Rounding``: nothing for the reference). The router's
    product, its scores and the short convolution's taps stay in float32
    under every rounding, as the policy keeps them. The step's ``key``
    goes unused: nothing here is drawn."""

    def head_loss(params, x, labels):
        logp = jax.nn.log_softmax(reference_logits(sizes, params, x, q),
                                  axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked[..., 0], axis=-1)

    return head_loss(params, x, y.astype(jnp.int32)), {}


def routing_disagreement(sizes, policy, traffic, seed):
    """The share of (token, expert layer) rows whose top-k SET differs
    between the program (``PROGRAM_EXPERT_IDS``, its set-up forward) and
    the reference's forward, both from the seed's weights on the seed's
    first batch. None where no program's ids are kept."""
    from benchmark import correctness
    if not PROGRAM_EXPERT_IDS:
        return None
    weights = make_weights(sizes, policy, seed)
    x = make_batches(sizes, policy, traffic, seed)[0][0]

    def forward(weights, x):
        ids = {}
        reference_logits(sizes, {n: v.astype(jnp.float32)
                                 for n, v in weights.items()},
                         x, correctness.Rounding, ids)
        return ids

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(forward)(weights, x)
    differ = rows = 0
    for name, ids in ref.items():
        a = jnp.sort(ids, axis=-1)
        b = jnp.sort(PROGRAM_EXPERT_IDS[name].reshape(ids.shape), axis=-1)
        differ += int(jnp.sum(jnp.any(a != b, axis=-1)))
        rows += ids.shape[0]
    return differ / rows
