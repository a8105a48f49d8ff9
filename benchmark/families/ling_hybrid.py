"""Family ``ling_hybrid``: the Ling hybrid decoder
(``inclusionAI/Ling-3.0-flash``, ``model_type: bailing_hybrid``) as
``mxnet_tpu.models.LingHybridLM`` builds it, trained on a per-position
cross-entropy, on ONE chip's share of a stated deployment (the
configuration's ``deployment``: which of the routed experts and which
slice of the vocabulary are held here).

Found by the family's name: the weights (one jitted call from the seed),
the program side (the Gluon net holding them, its loss, its batches),
the plain reference in straightforward ``jax.numpy`` (it imports nothing
of ``mxnet_tpu``), the matrix work a step needs from the shapes at two
FLOPs a multiply-add, and what the per-layer readers of this family's
cell compute from (``attention_products``, ``expert_products``,
``kda_block_work``, ``kda_scan_work``).

The layer equations (the configuration's ``assumed`` lists what the
source leaves open). Layer *i*: ``h = x + mixer_i(norm(x))``,
``out = h + ffn_i(norm(h))``; RMSNorm in float32 with a weight, eps
``rms_norm_eps``; one RMSNorm after the last layer; an untied head; the
loss is the mean cross-entropy over every position of a sequence, one
number a sequence. No bias anywhere.

- ``mixer_i`` where ``(i + 1) % layer_group_size != 0``, the Kimi delta
  rule (arXiv:2510.26692): per head of ``head_dim`` = d channels
  ``q = l2norm(silu(conv(x W_q)))``, ``k = l2norm(silu(conv(x W_k)))``,
  ``v = silu(conv(x W_v))``; ``conv`` one causal filter of
  ``short_conv_kernel_size`` taps a channel (``c[t] = sum_j w[:, j]
  z[t - 3 + j]``, zeros before a sequence's start); ``l2norm(x) = x /
  sqrt(sum x^2 + 1e-6)``; ``beta = sigmoid(x W_b)`` a head;
  ``log a = kda_lower_bound * sigmoid(exp(A_log)[h] * (x W_f +
  dt_bias))`` a channel; a state ``S`` (d x d) a head, zero at a
  sequence's start,
  ``S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t / sqrt(d)``; the output
  ``W_o (sigmoid(x W_g)[h] * rmsnorm_head(o_t))``, the norm over one
  head's channels with one weight of d. Here the recurrence runs TOKEN
  BY TOKEN (``lax.scan`` over T, ``jax.checkpoint`` round blocks of
  ``REF_TOKEN_BLOCK`` tokens so that its gradient fits).
- ``mixer_i`` elsewhere, latent attention (arXiv:2405.04434, no query
  latent): ``q = x W_q`` in heads of ``qk_nope_head_dim +
  qk_rope_head_dim``; ``c = x W_kva``; ``[k_nope, v] =
  rmsnorm(c[:kv_lora_rank]) W_kvb`` a head; ``k_rope = c[kv_lora_rank:]``
  shared by all heads; rotary positions in interleaved pairs on the
  rotary dimensions of q and on ``k_rope`` (theta ``rope_theta``);
  causal ``softmax((q_nope . k_nope + q_rope . k_rope) / sqrt(192)) v``
  in float32, dense, a block of queries at a time; times
  ``sigmoid(x W_g)[h]``; ``W_o``. Departure from the published code:
  DeepSeek's ``rope_interleave`` de-interleaves q and k and then turns
  halves; that differs from turning the pairs in place by one fixed
  permutation of both, which no score sees.
- ``ffn_i`` for ``i < first_k_dense_replace``: ``down(silu(gate x) *
  up x)`` of ``intermediate_size``.
- ``ffn_i`` after (arXiv:2412.19437): ``s = sigmoid(x W_r)`` over all
  the published experts in float32; ``s' = s + b`` (``b`` the selection
  bias: float32, no gradient, no update); the experts stand in
  ``n_group`` groups of consecutive ids; a group's score is the sum of
  its two largest ``s'``; the ``topk_group`` best groups stay (the lower
  index wins a tie); the ``num_experts_per_tok`` largest ``s'`` among
  their experts are chosen; weights ``s_e / (sum of the chosen s +
  1e-20)`` times ``routed_scaling_factor``; the experts held here (the
  same gated FFN, ``moe_intermediate_size``) add their weighted outputs,
  experts held elsewhere add nothing; one shared expert of
  ``moe_shared_expert_intermediate_size`` on every token, unweighted.

``sizes["planted_fault"]`` (no configuration has it; ``benchmark/
calibrate_faults.py`` and the tests set it) plants one fault in this
reference, which is then put in the program's place: ``decay_one`` (the
decay left at 1: a plain delta rule), ``no_group_limit`` (the k largest
``s'`` of all the experts), ``rope_key_unrotated`` (the shared key left
as projected). The harness's comparison sets norms side by side, and on
seeded weights a key that is not turned gives scores of the same
distribution, so the third fault is seen by ``block_witness`` alone:
the program's latent block against this reference's on one input, tensor
against tensor (the configuration's ``witness_limits``).

Parameter names are the net's attribute paths
(``layers.2.moe.w_gate``). The experts' matrices are stacked
``(held, in, out)``; every other matrix is ``(out, in)``; a filter is
``(H d, taps)``. The delta-rule mixer holds its seven projections as
its own leaves (``layers.0.kda.q_proj_weight``).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.seeding import seed_key

INIT_STD = 0.02
# the selection bias has no update rule in the source and ships as
# zeros; drawn at this scale so that leaving it out cannot read correct
# (``assumed.expert_bias``)
BIAS_STD = 0.015
# ``A_log`` is drawn N(0, RATE_STD) (the decay's rate a head 0.4-2.5)
# and ``dt_bias`` N(DT_MEAN, DT_STD): ``log a`` then lies about -0.09 in
# the median channel and between -0.02 and -0.5 in most, so a state
# remembers tens of tokens and no two heads forget alike
# (``assumed.initializer``)
RATE_STD, DT_MEAN, DT_STD = 0.5, -4.0, 0.5
ROUTER_EPS = 1e-20
L2_EPS = 1e-6
REF_QUERY_BLOCK = 1024  # queries a block in the reference's attention
REF_TOKEN_BLOCK = 64    # tokens a checkpointed block of its recurrence
FAULTS = ("decay_one", "no_group_limit", "rope_key_unrotated")


# ---------------------------------------------------------------------------
# the layers, from the sizes alone
# ---------------------------------------------------------------------------

def layer_plan(sizes):
    """One dict a layer: its index, whether its mixer is latent
    attention and whether its FFN is routed."""
    return [{"i": i, "latent": (i + 1) % sizes["layer_group_size"] == 0,
             "sparse": i >= sizes["first_k_dense_replace"]}
            for i in range(sizes["num_hidden_layers"])]


def experts_held(sizes):
    """``(first id, number held, number the router scores)``."""
    dep = sizes["deployment"]
    start, stop = dep["experts_held"]
    if stop - start != sizes["num_experts"]:
        raise ValueError("num_experts must count the experts held")
    return start, stop - start, dep["num_experts_published"]


def _widths(sizes):
    """``(hidden, heads, head_dim, nope, rope, v)``."""
    return (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["head_dim"], sizes["qk_nope_head_dim"],
            sizes["qk_rope_head_dim"], sizes["v_head_dim"])


def param_shapes(sizes):
    """name -> (shape, kind) for every leaf; kind ``matrix`` (bfloat16
    under the policy), ``router`` (float32), ``bias`` (the selection
    bias: float32, never trained), ``rate`` / ``dt`` (``A_log`` and
    ``dt_bias``, float32) or ``ones`` (a norm's weight, float32)."""
    c, h, d, nope, rope, dv = _widths(sizes)
    v, rank = sizes["vocab_size"], sizes["kv_lora_rank"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    fs = sizes["moe_shared_expert_intermediate_size"]
    taps = sizes["short_conv_kernel_size"]
    _, held, routed = experts_held(sizes)
    shapes = {"embed.weight": ((v, c), "matrix"),
              "norm.weight": ((c,), "ones"),
              "head.weight": ((v, c), "matrix")}

    def ffn(pre, width):
        shapes[f"{pre}.gate_proj.weight"] = ((width, c), "matrix")
        shapes[f"{pre}.up_proj.weight"] = ((width, c), "matrix")
        shapes[f"{pre}.down_proj.weight"] = ((c, width), "matrix")

    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}"
        shapes[f"{pre}.attn_norm.weight"] = ((c,), "ones")
        shapes[f"{pre}.mlp_norm.weight"] = ((c,), "ones")
        if layer["latent"]:
            for name, cout, cin in (
                    ("q_proj", h * (nope + rope), c),
                    ("kv_a_proj", rank + rope, c),
                    ("kv_b_proj", h * (nope + dv), rank),
                    ("g_proj", h, c), ("o_proj", c, h * dv)):
                shapes[f"{pre}.attn.{name}.weight"] = ((cout, cin),
                                                       "matrix")
            shapes[f"{pre}.attn.kv_norm.weight"] = ((rank,), "ones")
        else:
            for name in ("q_proj", "k_proj", "v_proj", "f_proj"):
                shapes[f"{pre}.kda.{name}_weight"] = ((h * d, c), "matrix")
            for name in ("b_proj", "g_proj"):
                shapes[f"{pre}.kda.{name}_weight"] = ((h, c), "matrix")
            for name in ("q_filter", "k_filter", "v_filter"):
                shapes[f"{pre}.kda.{name}"] = ((h * d, taps), "matrix")
            shapes[f"{pre}.kda.A_log"] = ((h,), "rate")
            shapes[f"{pre}.kda.dt_bias"] = ((h * d,), "dt")
            shapes[f"{pre}.kda.o_norm_weight"] = ((d,), "ones")
            shapes[f"{pre}.kda.o_proj_weight"] = ((c, h * d), "matrix")
        if layer["sparse"]:
            shapes[f"{pre}.moe.router_weight"] = ((routed, c), "router")
            shapes[f"{pre}.moe.expert_bias"] = ((routed,), "bias")
            shapes[f"{pre}.moe.w_gate"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_up"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_down"] = ((held, fe, c), "matrix")
            ffn(f"{pre}.moe.shared", fs)
        else:
            ffn(f"{pre}.mlp", f)
    return shapes


F32_LEAVES = ("norm.weight", "norm_weight", "router_weight", "expert_bias",
              "A_log", "dt_bias")


def param_dtype(name, policy):
    if policy == "f32":
        return jnp.float32
    if policy != "bf16_norm_router_f32":
        raise ValueError(f"ling_hybrid: unknown dtype policy {policy!r}")
    return jnp.float32 if name.endswith(F32_LEAVES) else jnp.bfloat16


def is_state(name):
    """Names among the weights that are no trained leaf: the selection
    bias, held fixed through a step."""
    return name.endswith("expert_bias")


def _act_bytes(sizes):
    return 4 if sizes["dtype_policy"] == "f32" else 2


def _count(sizes, latent):
    return sum(1 for layer in layer_plan(sizes)
               if layer["latent"] == latent)


def attention_products(sizes, traffic, windowed):
    """``(flops, bytes)`` a training step needs for the two attention
    products of the latent layers: scores ``nope + rope`` wide and
    weighted values ``v`` wide, two FLOPs a multiply-add over the causal
    half, forward once and backward twice; bytes the least HBM traffic
    in the activations' bytes: q, k, v and the result once each pass.
    The family has no windowed layer: nothing for ``windowed`` true."""
    if windowed:
        return 0.0, 0.0
    b, t = traffic["batch"], traffic["seq"]
    _, h, _, nope, rope, dv = _widths(sizes)
    layers = _count(sizes, True)
    return (float(layers * 3 * 2 * b * h * (t * (t + 1) // 2)
                  * (nope + rope + dv)),
            float(layers * 3 * _act_bytes(sizes) * b * t * h
                  * 2 * (nope + rope + dv)))


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


def kda_block_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the delta-rule
    mixers taken whole, each as ONE unit: its five wide projections (q,
    k, v, the decay's, the output's) forward once and backward twice;
    bytes: the mixer's input, its output and the five matrices once a
    pass. Whatever the compiler fuses inside the mixer, and whatever
    implements the recurrence, it cannot do less."""
    c, h, d = _widths(sizes)[:3]
    tok = traffic["batch"] * traffic["seq"]
    layers = _count(sizes, False)
    flops = 3 * 2 * tok * 5 * c * h * d
    nbytes = 3 * _act_bytes(sizes) * (2 * tok * c + 5 * c * h * d)
    return float(layers * flops), float(layers * nbytes)


def kda_scan_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the delta rule
    alone, whatever implements it: the recurrence's own multiply-adds a
    token a head (the state read by the key, the rank-one update, the
    decay, the state read by the query: 4 d^2 forward, twice that
    backward, two FLOPs each); bytes: q, k, v in the activations' bytes,
    ``log a`` and ``beta`` in float32 read and ``o`` written once
    forward; they and ``do`` read and the five gradients written once
    backward."""
    _, h, d = _widths(sizes)[:3]
    rows = traffic["batch"] * traffic["seq"] * h * _count(sizes, False)
    act = _act_bytes(sizes)
    operands = 3 * d * act + d * 4 + 4
    return (float(rows * 3 * 2 * 4 * d * d),
            float(rows * (operands + d * act          # forward
                          + 2 * operands + d * act)))  # backward


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add,
    forward once and backward twice: per token the projections, the
    dense FFN, the shared expert, the router and the head; per sequence
    the two attention products over the causal half; per token a head
    the delta rule's recurrence (``kda_scan_work``, a row of its own a
    layer); per routed row the three grouped products, at the rows
    uniform routing sends here. Bytes as in ``bert.matrix_layers``, in
    the activations' bytes. Nothing is counted for recomputation, for
    the filters, for rotary, norms, softmax, sorting, gathering or the
    update."""
    c, h, d, nope, rope, dv = _widths(sizes)
    v, rank = sizes["vocab_size"], sizes["kv_lora_rank"]
    tok, nbytes = traffic["batch"] * traffic["seq"], _act_bytes(sizes)
    _, _, routed = experts_held(sizes)

    def product(name, cin, cout):
        return (name, float(3 * 2 * tok * cin * cout),
                float(3 * nbytes * (tok * (cin + cout) + cin * cout)))

    def ffn(pre, width):
        return [product(f"{pre}.gate_proj", c, width),
                product(f"{pre}.up_proj", c, width),
                product(f"{pre}.down_proj", width, c)]

    out = []
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}"
        if layer["latent"]:
            flops, moved = attention_products(sizes, traffic, False)
            n = _count(sizes, True)
            out += [product(f"{pre}.attn.q_proj", c, h * (nope + rope)),
                    product(f"{pre}.attn.kv_a_proj", c, rank + rope),
                    product(f"{pre}.attn.kv_b_proj", rank, h * (nope + dv)),
                    product(f"{pre}.attn.g_proj", c, h),
                    (f"{pre}.attn.products", flops / n, moved / n),
                    product(f"{pre}.attn.o_proj", h * dv, c)]
        else:
            flops, moved = kda_scan_work(sizes, traffic)
            n = _count(sizes, False)
            out += [product(f"{pre}.kda.qkvf_proj", c, 4 * h * d),
                    product(f"{pre}.kda.bg_proj", c, 2 * h),
                    (f"{pre}.kda.scan", flops / n, moved / n),
                    product(f"{pre}.kda.o_proj", h * d, c)]
        if layer["sparse"]:
            out.append(product(f"{pre}.moe.router", c, routed))
            out.append((f"{pre}.moe.experts",)
                       + expert_products(sizes,
                                         expected_rows(sizes, traffic)))
            out += ffn(f"{pre}.moe.shared",
                       sizes["moe_shared_expert_intermediate_size"])
        else:
            out += ffn(f"{pre}.mlp", sizes["intermediate_size"])
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
    """All leaves drawn in one jitted call on the device, in the type
    they are trained in, and handed over as HOST arrays: every matrix,
    filter and router normal with std 0.02, the selection bias normal
    with std ``BIAS_STD``, ``A_log`` normal with std ``RATE_STD``,
    ``dt_bias`` normal about ``DT_MEAN``, norm weights one.

    On the host because the harness keeps the weights it started from
    beside the reference's three steps (to take the parameters' change
    at the end), and at this size a step of the reference fills the
    chip without them: its arguments (parameters and Adam's moments),
    its results (the same again, and the gradients) and 2.2 GB of
    temporaries are 13.8 of the chip's 15.75 GB, and 1.64 GB of idle
    weights on the device beside them leave no room. A step takes host
    arrays as it takes device ones; the program copies them into its
    net."""
    shapes = param_shapes(sizes)
    draw = {"matrix": (0.0, INIT_STD), "router": (0.0, INIT_STD),
            "bias": (0.0, BIAS_STD), "rate": (0.0, RATE_STD),
            "dt": (DT_MEAN, DT_STD)}

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            dt = param_dtype(name, policy)
            if kind == "ones":
                leaves[name] = jnp.ones(shape, dt)
            else:
                mean, std = draw[kind]
                leaves[name] = (mean + std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dt)
        return leaves

    return jax.device_get(jax.jit(build)(seed_key(seed, 0)))


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
    """``models.LingHybridLM.from_config`` on ``ctx`` holding
    ``weights``, and its loss. One untimed eager forward on the first
    batch follows: that is where the expert layers fill their telemetry
    gauges (rows routed here, the fullest expert over the mean, the
    shares of tokens whose choice the bias and the group limit
    changed)."""
    from mxnet_tpu import autograd, gluon, models
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = models.LingHybridLM.from_config(sizes)
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


def _rope_pairs(x, theta):
    """``x`` (B, T, H, D) with every head turned by its position, the
    dimensions 2i and 2i + 1 together by ``t * theta^(-2i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(qh, kh, vh, q):
    """Dense causal attention, one head and one block of queries at a
    time. ``qh`` / ``kh`` (B, H, T, D); ``vh`` (B, H, T, Dv)."""
    b, h, t, d = qh.shape
    if b == 0:  # the fault that leaves half of a batch of one out
        return jnp.zeros(vh.shape, qh.dtype)
    bq = REF_QUERY_BLOCK if t % REF_QUERY_BLOCK == 0 else t
    nq = t // bq
    blocks = qh.reshape(b * h * nq, bq, d)
    kf, vf = kh.reshape(b * h, t, d), vh.reshape(b * h, t, vh.shape[-1])
    kpos = jnp.arange(t)[None, :]

    def one(args):
        block, item = args
        head, start = item // nq, (item % nq) * bq
        s = q.out(q.inp(block) @ q.inp(kf[head]).T) / math.sqrt(d)
        seen = kpos <= start + jnp.arange(bq)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return q.out(q.inp(p) @ q.inp(vf[head]))

    out = jax.lax.map(jax.checkpoint(one),
                      (blocks, jnp.arange(b * h * nq)))
    return out.reshape(b, h, t, vh.shape[-1])


def _conv_silu(x, w):
    """``silu`` of one causal filter a channel: the taps as shifted adds,
    zeros before a sequence's start. ``x`` (B, T, C); ``w`` (C, taps)."""
    t, taps = x.shape[1], w.shape[1]
    z = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * z[:, j:j + t] for j in range(taps)))


def delta_rule(qh, kh, vh, log_a, beta):
    """The gated delta rule token by token. ``qh``, ``kh``, ``vh``,
    ``log_a`` (B, T, H, D), ``beta`` (B, T, H) -> (B, T, H, D), float32
    state; a block of ``REF_TOKEN_BLOCK`` tokens is checkpointed."""
    b, t, h, d = qh.shape
    if b == 0:
        return jnp.zeros_like(vh)
    block = REF_TOKEN_BLOCK if t % REF_TOKEN_BLOCK == 0 else t

    def token(state, x):
        q_t, k_t, v_t, log_a_t, beta_t = x
        state = jnp.exp(log_a_t)[..., None] * state       # diag(a) S
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)    # S^T k
        state = state + k_t[..., None] \
            * (beta_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state) \
            / math.sqrt(d)

    def tokens(state, xs):
        # a token keeps the state it entered with and nothing else
        return jax.lax.scan(jax.checkpoint(token), state, xs)

    xs = [jnp.moveaxis(a, 1, 0).reshape((t // block, block) + a.shape[:1]
                                        + a.shape[2:])
          for a in (qh, kh, vh, log_a, beta)]
    _, o = jax.lax.scan(jax.checkpoint(tokens),
                        jnp.zeros((b, h, d, d), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _gated_ffn(x, gate, up, down, q):
    """``gate`` / ``up`` (in, out), ``down`` (out, in) as (F, C)."""
    hidden = jax.nn.silu(q.out(q.inp(x) @ q.inp(gate))) \
        * q.out(q.inp(x) @ q.inp(up))
    return q.out(q.inp(q.act(hidden)) @ q.inp(down))


def routing(sizes, x, router_w, bias):
    """``(weights, expert ids)``, each (N, k), of the tokens ``x`` in
    float32: sigmoid scores over all the router's outputs; with the
    bias added, the groups' scores (the sum of a group's two largest),
    the best ``topk_group`` groups, the k largest inside them; the
    unbiased scores of those renormalised, times the scaling factor."""
    k = sizes["num_experts_per_tok"]
    n_group, topk_group = sizes["n_group"], sizes["topk_group"]
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32).T,
                     precision="highest")
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)
    if sizes.get("planted_fault") != "no_group_limit":
        n, e = biased.shape
        per_group = biased.reshape(n, n_group, e // n_group)
        group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        stays = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], best].set(True)
        biased = jnp.where(stays[:, :, None], per_group,
                           -jnp.inf).reshape(n, e)
    _, top_i = jax.lax.top_k(biased, k)
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

    def one(acc, expert):
        # the expert's matrices come as the scan's inputs: closed over,
        # their gradients would be summed into float32 buffers of all
        # the held experts' size, which the compiler allocates early
        e, gate, up, down = expert
        w = jnp.sum(jnp.where(ids == start + e, weights, 0.0), axis=-1)
        y = _gated_ffn(x, gate, up, down, q)
        return acc + w[:, None].astype(x.dtype) * y, None

    acc, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(x),
        (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    return acc, ids


def _shared_expert(x, p, q):
    return _gated_ffn(x, p["shared.gate_proj.weight"].T,
                      p["shared.up_proj.weight"].T,
                      p["shared.down_proj.weight"].T, q)


def _kda_mixer(sizes, x, p, q):
    b, t, _ = x.shape
    _, h, d = _widths(sizes)[:3]
    f32 = jnp.float32

    def dense(name):
        return q.out(q.inp(x) @ q.inp(p[f"{name}_weight"]).T)

    def heads(a):
        return a.reshape(b, t, h, a.shape[-1] // h)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                            + L2_EPS)

    qh = unit(heads(_conv_silu(dense("q_proj").astype(f32),
                               p["q_filter"].astype(f32))))
    kh = unit(heads(_conv_silu(dense("k_proj").astype(f32),
                               p["k_filter"].astype(f32))))
    vh = heads(_conv_silu(dense("v_proj").astype(f32),
                          p["v_filter"].astype(f32)))
    rate = jnp.exp(p["A_log"].astype(f32))[:, None]
    log_a = sizes["kda_lower_bound"] * jax.nn.sigmoid(
        rate * heads(dense("f_proj").astype(f32)
                     + p["dt_bias"].astype(f32)))
    if sizes.get("planted_fault") == "decay_one":
        log_a = jnp.zeros_like(log_a)
    beta = jax.nn.sigmoid(dense("b_proj").astype(f32))
    o = delta_rule(q.act(qh).astype(f32), q.act(kh).astype(f32),
                   q.act(vh).astype(f32), log_a, beta)
    o = _rms(o, p["o_norm_weight"].astype(f32), sizes["rms_norm_eps"]) \
        * jax.nn.sigmoid(dense("g_proj").astype(f32))[..., None]
    o = q.act(o.astype(x.dtype).reshape(b, t, h * d))
    return q.out(q.inp(o) @ q.inp(p["o_proj_weight"]).T)


def _latent_mixer(sizes, x, p, q):
    b, t, _ = x.shape
    _, h, _, nope, rope, dv = _widths(sizes)
    rank, theta = sizes["kv_lora_rank"], sizes["rope_theta"]

    def dense(a, name):
        return q.out(q.inp(a) @ q.inp(p[f"{name}.weight"]).T)

    qh = dense(x, "q_proj").reshape(b, t, h, nope + rope)
    latent = dense(x, "kv_a_proj")
    kv = dense(q.act(_rms(latent[..., :rank], p["kv_norm.weight"],
                          sizes["rms_norm_eps"])),
               "kv_b_proj").reshape(b, t, h, nope + dv)
    k_rope = latent[..., rank:][:, :, None, :]
    if sizes.get("planted_fault") != "rope_key_unrotated":
        k_rope = _rope_pairs(k_rope, theta)
    qh = jnp.concatenate([qh[..., :nope],
                          _rope_pairs(qh[..., nope:], theta)], axis=-1)
    kh = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_rope, (b, t, h, rope))],
                         axis=-1)
    o = _attention(q.act(qh).transpose(0, 2, 1, 3),
                   q.act(kh).transpose(0, 2, 1, 3),
                   q.act(kv[..., nope:]).transpose(0, 2, 1, 3), q)
    gate = jax.nn.sigmoid(dense(x, "g_proj").astype(jnp.float32))
    o = (o.transpose(0, 2, 1, 3).astype(jnp.float32)
         * gate[..., None]).astype(x.dtype)
    return dense(q.act(o.reshape(b, t, h * dv)), "o_proj")


def _own(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items()
            if k.startswith(prefix)}


def _mixer_half(sizes, layer, h, p, q):
    """``h + mixer(norm(h))`` from the layer's leaves ``p``."""
    x = q.act(_rms(h, p["attn_norm.weight"], sizes["rms_norm_eps"]))
    if layer["latent"]:
        return h + _latent_mixer(sizes, x, _own(p, "attn."), q)
    return h + _kda_mixer(sizes, x, _own(p, "kda."), q)


def _ffn_half(sizes, layer, h, p, q):
    """``(h + ffn(norm(h)), the expert ids the router chose (N, k) or
    None)`` from the layer's leaves ``p``."""
    b, t, c = h.shape
    x = q.act(_rms(h, p["mlp_norm.weight"], sizes["rms_norm_eps"]))
    if not layer["sparse"]:
        return h + _gated_ffn(x, p["mlp.gate_proj.weight"].T,
                              p["mlp.up_proj.weight"].T,
                              p["mlp.down_proj.weight"].T, q), None
    flat, own = x.reshape(b * t, c), _own(p, "moe.")
    routed, ids = _experts(sizes, flat, own, q)
    return h + (routed + _shared_expert(flat, own, q)).reshape(b, t, c), ids


def _layer(sizes, layer, h, p, q):
    """``(the layer's output, the expert ids its router chose (N, k) or
    None)``."""
    return _ffn_half(sizes, layer, _mixer_half(sizes, layer, h, p, q), p, q)


def _stored(sizes, name, value):
    """``value`` in the dtype its leaf is stored in. The harness hands
    the reference every leaf already cast to the dtype to compute in; a
    leaf stored in bfloat16 goes back to it without loss, and each
    checkpointed block below casts again inside itself, so that what is
    kept from the forward pass to the backward pass is the stored leaf
    (which is alive anyway) and never a float32 copy of every matrix:
    3.3 GB at the cell's size, which the chip does not have."""
    return value.astype(param_dtype(name, sizes["dtype_policy"]))


def _kept_whole(fn):
    """``fn(h, own) -> h`` rematerialised in the backward pass, which
    keeps ``h`` and ``own`` alone and may not start its recomputation
    before the block's cotangent has arrived (the barrier): without it
    the compiler is free to rebuild every block's float32 leaves at the
    start of the backward pass and hold them all."""
    @jax.custom_vjp
    def run(h, own):
        return fn(h, own)

    def forward(h, own):
        return fn(h, own), (h, own)

    def backward(kept, g):
        h, own, g = jax.lax.optimization_barrier((*kept, g))
        return jax.vjp(fn, h, own)[1](g)

    run.defvjp(forward, backward)
    return run


def _hidden(sizes, params, x, q, expert_ids=None):
    """The final norm's output (B, T, C) on token ids ``x``."""
    compute = params["embed.weight"].dtype
    h = q.act(_stored(sizes, "embed.weight",
                      params["embed.weight"])[x].astype(compute))
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}."
        own = {k[len(pre):]: _stored(sizes, k, v)
               for k, v in params.items() if k.startswith(pre)}
        kept = {k: params[pre + k].dtype for k in own}

        def block(h, own, layer=layer, kept=kept):
            return _layer(sizes, layer, h, {k: v.astype(kept[k])
                                            for k, v in own.items()}, q)

        if expert_ids is not None and layer["sparse"]:
            expert_ids[pre[:-1]] = block(h, own)[1]
        # one layer's activations at a time are kept for backward
        h = _kept_whole(lambda h, own, block=block: block(h, own)[0])(
            h, own)
    return q.act(_rms(h, params["norm.weight"], sizes["rms_norm_eps"]))


def reference_logits(sizes, params, x, q, expert_ids=None):
    """Float32 logits (B, T, V) of the network on token ids ``x``;
    ``expert_ids``, a dict, is filled with each sparse layer's chosen
    expert ids under the layer's name (``layers.2``)."""
    h = _hidden(sizes, params, x, q, expert_ids)
    return q.out(q.inp(h) @ q.inp(params["head.weight"]).T
                 ).astype(jnp.float32)


def reference_loss(sizes, params, x, y, q, key):
    """Per-sequence mean cross-entropy of the network, and no state.
    ``params`` hold every leaf in the dtype to compute in; ``q.inp`` is
    called on every operand of a matrix product, ``q.out`` on its result
    and ``q.act`` on every array kept between products
    (``correctness.Rounding``: nothing for the reference). The router's
    product and scores, the filters, the decay and the recurrence's
    state stay in float32 under every rounding, as the policy keeps
    them. The step's ``key`` goes unused: nothing here is drawn. The
    head and the loss go a block of ``REF_QUERY_BLOCK`` positions at a
    time, so that one block's logits are alive and not the sequence's."""
    h = _hidden(sizes, params, x, q)
    b, t, c = h.shape
    labels = y.astype(jnp.int32)
    block = REF_QUERY_BLOCK if t % REF_QUERY_BLOCK == 0 else t
    head = _stored(sizes, "head.weight", params["head.weight"])
    compute = params["head.weight"].dtype

    def picked(args):
        h, labels = args
        logits = q.out(q.inp(h) @ q.inp(head.astype(compute)).T
                       ).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    blocks = (h.reshape(b, t // block, block, c).swapaxes(0, 1),
              labels.reshape(b, t // block, block).swapaxes(0, 1))
    logp = jax.lax.map(jax.checkpoint(picked), blocks)   # (blocks, B, block)
    return -jnp.mean(logp.swapaxes(0, 1).reshape(b, t), axis=-1), {}


def block_witness(sizes, policy, weights, batches, ctx):
    """``{"latent_block_gap": ..}``: the program's latent block
    (``models.LingLatentAttention`` on ``ctx`` holding the seed's leaves
    of the cut's latent layer) against ``_latent_mixer`` in float32, on
    the layer's normed embedding of the first batch's tokens; the
    distance between the two outputs over the reference's norm. What the
    step's comparison of norms cannot see of the block (a key left
    unrotated, a wrong pairing of the rotary dimensions, a wrong
    position) moves every score here."""
    from mxnet_tpu import autograd, models
    from mxnet_tpu.ndarray.ndarray import _wrap
    from benchmark import correctness

    pre = next(f"layers.{layer['i']}." for layer in layer_plan(sizes)
               if layer["latent"])
    own = _own(weights, pre + "attn.")
    c, heads, _, nope, rope, dv = _widths(sizes)
    block = models.LingLatentAttention(
        c, heads, nope, rope, dv, sizes["kv_lora_rank"],
        sizes["rope_theta"], sizes["rms_norm_eps"])
    block.initialize(ctx=ctx)
    for name, p in block._collect_params_with_prefix().items():
        dt = str(jnp.dtype(param_dtype(pre + "attn." + name, policy)))
        if str(p.data().dtype) != dt:
            p.cast(dt)
        p.set_data(_wrap(jnp.array(own[name], copy=True)))
    embed = jnp.asarray(weights["embed.weight"])
    x = _rms(embed[batches[0][0]].astype(jnp.float32),
             jnp.asarray(weights[pre + "attn_norm.weight"]),
             sizes["rms_norm_eps"]).astype(embed.dtype)
    with autograd.pause():
        got = block(_wrap(x))._data.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x, own: _latent_mixer(
            sizes, x, own, correctness.Rounding))(
                x.astype(jnp.float32),
                {n: jnp.asarray(w, jnp.float32) for n, w in own.items()})
    return {"latent_block_gap": float(jnp.linalg.norm(got - ref)
                                      / jnp.linalg.norm(ref))}


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
