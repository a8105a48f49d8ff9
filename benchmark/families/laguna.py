"""Family ``laguna``: the Laguna decoder (``poolside/Laguna-XS.2``,
``model_type: laguna``) as ``mxnet_tpu.models.LagunaLM`` builds it,
trained on a per-position cross-entropy, on ONE chip's share of a stated
deployment (the configuration's ``deployment``: which of the routed
experts and which slice of the vocabulary are held here).

Found by the family's name: the weights (one jitted call from the seed),
the program side (the Gluon net holding them, its loss, its batches),
the plain reference in straightforward ``jax.numpy`` (it imports nothing
of ``mxnet_tpu``), the matrix work a step needs from the shapes at two
FLOPs a multiply-add, and what the per-layer readers of this family's
cells compute from (``attention_products``, ``expert_products``).

The layer equations (the configuration's ``assumed`` lists what the
source leaves open). Per layer ``x + attn(norm(x))`` then
``x + ffn(norm(x))``, RMSNorm with eps ``rms_norm_eps``; a final
RMSNorm; an untied head; the loss is the mean cross-entropy over every
position of a sequence, one number a sequence.

- Attention of layer *i*: ``num_attention_heads_per_layer[i]`` query
  heads of ``head_dim`` over ``num_key_value_heads`` key/value heads
  (query head *h* reads key/value head ``h // group``); rotary positions
  by the layer's kind (``rope_parameters``; rotate-half layout over the
  first ``partial_rotary_factor`` of a head's dimensions; yarn's
  frequencies and ``attention_factor`` as ``transformers`` computes
  them); scores / sqrt(head_dim), causal, on ``sliding_attention``
  layers keys no further back than ``sliding_window - 1`` positions;
  softmax in float32; each head's output times
  ``sigmoid(norm(x) W_g)[h]``; ``o_proj``. No bias.
- ``dense`` FFN: ``down(silu(gate(x)) * up(x))`` of
  ``intermediate_size``.
- ``sparse`` FFN: router logits over all the published experts in
  float32, softmax, the 8 largest, renormalised over the 8, times
  ``moe_routed_scaling_factor``; the experts held here (the same gated
  FFN, ``moe_intermediate_size``) add their weighted outputs, experts
  held elsewhere add nothing; one shared expert
  (``shared_expert_intermediate_size``) on every token, unweighted.

Parameter names are the net's attribute paths
(``layers.1.moe.w_gate``). The experts' matrices are stacked
``(held, in, out)``; every other matrix is ``(out, in)``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.seeding import seed_key

INIT_STD = 0.02
REF_QUERY_BLOCK = 1024  # queries a block in the reference's attention


# ---------------------------------------------------------------------------
# the layers, from the sizes alone
# ---------------------------------------------------------------------------

def layer_plan(sizes):
    """One dict a layer: its index, query heads, window (None: full),
    the name of its rotary scheme and whether its FFN is sparse."""
    n = sizes["num_hidden_layers"]
    heads = sizes.get("num_attention_heads_per_layer") \
        or [sizes["num_attention_heads"]] * n
    plan = []
    for i in range(n):
        kind = sizes["layer_types"][i]
        plan.append({
            "i": i, "heads": heads[i], "kind": kind,
            "window": sizes["sliding_window"]
            if kind == "sliding_attention" else None,
            "sparse": sizes["mlp_layer_types"][i] == "sparse"})
    return plan


def experts_held(sizes):
    """``(first id, number held, number the router scores)``."""
    dep = sizes["deployment"]
    start, stop = dep["experts_held"]
    if stop - start != sizes["num_experts"]:
        raise ValueError("num_experts must count the experts held")
    return start, stop - start, dep["num_experts_published"]


def param_shapes(sizes):
    """name -> (shape, kind) for every leaf; kind ``matrix`` (bfloat16
    under the policy), ``router`` (float32) or ``ones`` (an RMSNorm's
    weight, float32)."""
    c, v, d = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    fs = sizes["shared_expert_intermediate_size"]
    _, held, routed = experts_held(sizes)
    shapes = {"embed.weight": ((v, c), "matrix"),
              "norm.weight": ((c,), "ones"),
              "head.weight": ((v, c), "matrix")}
    for layer in layer_plan(sizes):
        pre, h = f"layers.{layer['i']}", layer["heads"]
        shapes[f"{pre}.attn_norm.weight"] = ((c,), "ones")
        shapes[f"{pre}.mlp_norm.weight"] = ((c,), "ones")
        for name, cout, cin in (("q_proj", h * d, c), ("k_proj", kv * d, c),
                                ("v_proj", kv * d, c), ("g_proj", h, c),
                                ("o_proj", c, h * d)):
            shapes[f"{pre}.attn.{name}.weight"] = ((cout, cin), "matrix")
        if layer["sparse"]:
            shapes[f"{pre}.moe.router_weight"] = ((routed, c), "router")
            shapes[f"{pre}.moe.w_gate"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_up"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_down"] = ((held, fe, c), "matrix")
            ffn, width = f"{pre}.moe.shared", fs
        else:
            ffn, width = f"{pre}.mlp", f
        shapes[f"{ffn}.gate_proj.weight"] = ((width, c), "matrix")
        shapes[f"{ffn}.up_proj.weight"] = ((width, c), "matrix")
        shapes[f"{ffn}.down_proj.weight"] = ((c, width), "matrix")
    return shapes


def param_dtype(name, policy):
    if policy == "f32":
        return jnp.float32
    if policy != "bf16_norm_router_f32":
        raise ValueError(f"laguna: unknown dtype policy {policy!r}")
    if name.endswith("norm.weight") or name.endswith("router_weight"):
        return jnp.float32
    return jnp.bfloat16


def is_state(name):
    """Leaves the step rewrites without a gradient: none here."""
    return False


def allowed_pairs(t, window):
    """(query, key) pairs a head's mask allows over ``t`` positions:
    the causal half, or the band of ``window`` keys a query."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _attention_work(sizes, traffic, layer):
    """``(flops, bytes)`` of one layer's two attention products a
    training step: FLOPs at two a multiply-add over the pairs the mask
    allows, forward once and backward twice; bytes the least HBM
    traffic in the activations' bytes: q, k, v and the result once each
    pass, since the algorithm need not write its scores."""
    b, t, d = traffic["batch"], traffic["seq"], sizes["head_dim"]
    h, kv = layer["heads"], sizes["num_key_value_heads"]
    return (float(3 * 2 * 2 * b * h * allowed_pairs(t, layer["window"]) * d),
            float(3 * _act_bytes(sizes) * b * t * d * (2 * h + 2 * kv)))


def attention_products(sizes, traffic, windowed):
    """``(flops, bytes)`` a training step needs for the two attention
    products (scores, weighted values) of the sliding layers
    (``windowed`` true) or of the full ones: the sum of
    ``_attention_work`` over them."""
    work = [_attention_work(sizes, traffic, layer)
            for layer in layer_plan(sizes)
            if (layer["window"] is not None) == windowed]
    return sum(f for f, _ in work), sum(b for _, b in work)


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


def _act_bytes(sizes):
    return 4 if sizes["dtype_policy"] == "f32" else 2


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add,
    forward once and backward twice: per token the projections, the
    gate, the dense or shared FFN, the router and the head; per sequence
    the two attention products over the pairs the mask allows (the
    causal half, the window's band); per routed row the three grouped
    products, at the rows uniform routing sends here. Bytes as in
    ``bert.matrix_layers``, in the activations' bytes. Nothing is
    counted for recomputation, for rotary, norms, softmax, sorting,
    gathering or the update."""
    c, v, d = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    b, t = traffic["batch"], traffic["seq"]
    tok, nbytes = b * t, _act_bytes(sizes)
    _, _, routed = experts_held(sizes)

    def product(name, cin, cout):
        return (name, float(3 * 2 * tok * cin * cout),
                float(3 * nbytes * (tok * (cin + cout) + cin * cout)))

    def gated(name, width):
        return [product(f"{name}.gate_proj", c, width),
                product(f"{name}.up_proj", c, width),
                product(f"{name}.down_proj", width, c)]

    out = []
    for layer in layer_plan(sizes):
        pre, h = f"layers.{layer['i']}", layer["heads"]
        out.append(product(f"{pre}.attn.qkvg", c, (h + 2 * kv) * d + h))
        out.append((f"{pre}.attn.products",)
                   + _attention_work(sizes, traffic, layer))
        out.append(product(f"{pre}.attn.o_proj", h * d, c))
        if layer["sparse"]:
            out.append(product(f"{pre}.moe.router", c, routed))
            out.append((f"{pre}.moe.experts",)
                       + expert_products(sizes,
                                         expected_rows(sizes, traffic)))
            out += gated(f"{pre}.moe.shared",
                         sizes["shared_expert_intermediate_size"])
        else:
            out += gated(f"{pre}.mlp", sizes["intermediate_size"])
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
    every matrix normal with std 0.02, RMSNorm weights one."""
    shapes = param_shapes(sizes)

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            dt = param_dtype(name, policy)
            if kind == "ones":
                leaves[name] = jnp.ones(shape, dt)
            else:
                leaves[name] = (INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dt)
        return leaves

    return jax.jit(build)(seed_key(seed, 0))


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
    """``models.LagunaLM.from_config`` on ``ctx`` holding ``weights``,
    and its loss. One untimed eager forward on the first batch follows:
    that is where the expert layers fill their telemetry gauges (rows
    routed here, the fullest expert over the mean, rows dropped)."""
    from mxnet_tpu import autograd, gluon, models
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = models.LagunaLM.from_config(sizes)
    net.initialize(ctx=ctx)
    params = net._collect_params_with_prefix()
    if set(params) != set(weights):
        raise RuntimeError("the net's parameters and the benchmark's "
                           "differ: " + str(sorted(set(params)
                                                   ^ set(weights))[:6]))
    for name, p in params.items():
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


def _inverse_frequencies(dim, rope):
    """Of one rotary scheme, and the factor its cos and sin carry."""
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    if rope.get("rope_type", "default") != "yarn":
        return 1.0 / rope["rope_theta"] ** exponents, 1.0
    base, factor = rope["rope_theta"], rope["factor"]
    span = rope["original_max_position_embeddings"]
    freqs = base ** exponents

    def dim_of(rotations):  # the dimension that turns so often over span
        return dim * math.log(span / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    blend = (1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)
    scale = rope.get("attention_factor")
    return blend, (0.1 * math.log(factor) + 1.0) if scale is None else scale


def _rope(x, rope, head_dim):
    """``x`` (B, T, H, D) with its first rotary dimensions turned."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    inv, scale = _inverse_frequencies(dim, rope)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang) * scale, x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * scale, x.dtype)[None, :, None, :]
    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _attention(qh, kh, vh, window, q):
    """Dense masked attention, one head and one block of queries at a
    time. ``qh`` (B, H, T, D); ``kh`` / ``vh`` (B, Hkv, T, D)."""
    b, h, t, d = qh.shape
    if b == 0:  # the fault that leaves half of a batch of one out
        return jnp.zeros_like(qh)
    group = h // kh.shape[1]
    bq = REF_QUERY_BLOCK if t % REF_QUERY_BLOCK == 0 else t
    nq = t // bq
    blocks = qh.reshape(b * h * nq, bq, d)
    kf, vf = kh.reshape(-1, t, d), vh.reshape(-1, t, d)
    index = jnp.arange(b * h * nq)
    kpos = jnp.arange(t)[None, :]

    def one(args):
        block, item = args
        head, start = item // nq, (item % nq) * bq
        kv = (head // h) * kh.shape[1] + (head % h) // group
        s = q.out(q.inp(block) @ q.inp(kf[kv]).T) / math.sqrt(d)
        qpos = start + jnp.arange(bq)[:, None]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (qpos - kpos < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return q.out(q.inp(p) @ q.inp(vf[kv]))

    out = jax.lax.map(jax.checkpoint(one), (blocks, index))
    return out.reshape(b, h, t, d)


def _gated_ffn(x, gate, up, down, q):
    """``gate`` / ``up`` (in, out), ``down`` (out, in) as (F, C)."""
    hidden = jax.nn.silu(q.out(q.inp(x) @ q.inp(gate))) \
        * q.out(q.inp(x) @ q.inp(up))
    return q.out(q.inp(q.act(hidden)) @ q.inp(down))


def routing(sizes, x, router_w):
    """``(weights, expert ids)``, each (N, k), of the tokens ``x`` in
    float32: softmax over all the router's outputs, the k largest,
    renormalised over the k, times the scaling factor."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32).T,
                     precision="highest")
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True) \
        * sizes["moe_routed_scaling_factor"], top_i


def _experts(sizes, x, p, q, held=None):
    """The held experts' part of the routed FFN for tokens ``x``
    (N, C): a loop over the experts, each on every token under a mask of
    its routing weight; no sort, no grouping. ``held``: (first id,
    count), the configuration's by default."""
    start, count = held or experts_held(sizes)[:2]
    weights, ids = routing(sizes, x, p["router_weight"])

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
    d, kv = sizes["head_dim"], sizes["num_key_value_heads"]
    heads, eps = layer["heads"], sizes["rms_norm_eps"]
    rope = sizes["rope_parameters"][layer["kind"]]

    def dense(x, name):
        return q.out(q.inp(x) @ q.inp(p[f"{name}.weight"]).T)

    x = q.act(_rms(h, p["attn_norm.weight"], eps))
    qh = _rope(dense(x, "attn.q_proj").reshape(b, t, heads, d), rope, d)
    kh = _rope(dense(x, "attn.k_proj").reshape(b, t, kv, d), rope, d)
    vh = dense(x, "attn.v_proj").reshape(b, t, kv, d)
    o = _attention(q.act(qh).transpose(0, 2, 1, 3),
                   q.act(kh).transpose(0, 2, 1, 3),
                   q.act(vh).transpose(0, 2, 1, 3), layer["window"], q)
    gate = jax.nn.sigmoid(dense(x, "attn.g_proj"))       # (B, T, H)
    o = o.transpose(0, 2, 1, 3) * gate[..., None]
    h = h + dense(q.act(o.reshape(b, t, heads * d)), "attn.o_proj")

    x = q.act(_rms(h, p["mlp_norm.weight"], eps))
    if not layer["sparse"]:
        return h + _gated_ffn(x, p["mlp.gate_proj.weight"].T,
                              p["mlp.up_proj.weight"].T,
                              p["mlp.down_proj.weight"].T, q), None
    flat = x.reshape(b * t, c)
    own = {k[len("moe."):]: v for k, v in p.items() if k.startswith("moe.")}
    routed, ids = _experts(sizes, flat, own, q)
    shared = _gated_ffn(flat, own["shared.gate_proj.weight"].T,
                        own["shared.up_proj.weight"].T,
                        own["shared.down_proj.weight"].T, q)
    return h + (routed + shared).reshape(b, t, c), ids


def reference_logits(sizes, params, x, q, expert_ids=None):
    """Float32 logits (B, T, V) of the network on token ids ``x``;
    ``expert_ids``, a dict, is filled with each sparse layer's chosen
    expert ids under the layer's name (``layers.1``)."""
    h = q.act(params["embed.weight"][x])
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
    h = q.act(_rms(h, params["norm.weight"], sizes["rms_norm_eps"]))
    return q.out(q.inp(h) @ q.inp(params["head.weight"]).T) \
        .astype(jnp.float32)


def reference_loss(sizes, params, x, y, q, key):
    """Per-sequence mean cross-entropy of the network, and no state.
    ``params`` hold every leaf in the dtype to compute in; ``q.inp`` is
    called on every operand of a matrix product, ``q.out`` on its result
    and ``q.act`` on every array kept between products
    (``correctness.Rounding``: nothing for the reference). The router's
    product stays in float32 under every rounding, as the policy keeps
    it. The step's ``key`` goes unused: nothing here is drawn."""

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
