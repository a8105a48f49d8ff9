"""Family ``nemotron_h``: the Nemotron-H hybrid decoder
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, ``model_type:
nemotron_h``) as ``mxnet_tpu.models.NemotronHLM`` builds it, trained on a
per-position cross-entropy, on ONE chip's share of a stated deployment
(the configuration's ``deployment``: which of the routed experts and
which slice of the vocabulary are held here).

Found by the family's name: the weights (one jitted call from the seed),
the program side (the Gluon net holding them, its loss, its batches),
the plain reference in straightforward ``jax.numpy`` (it imports nothing
of ``mxnet_tpu``), the matrix work a step needs from the shapes at two
FLOPs a multiply-add, and what the per-layer readers of this family's
cell compute from (``attention_products``, ``expert_products``,
``mamba_block_work``, ``ssd_scan_work``).

The layer equations (the configuration's ``assumed`` lists what the
source leaves open). Layer *i* is ``x + block_i(rmsnorm(x))``,
``block_i`` chosen by character *i* of ``hybrid_override_pattern``;
RMSNorm in float32 with a weight, eps ``layer_norm_epsilon``; one RMSNorm
after the last layer; an untied head; the loss is the mean cross-entropy
over every position of a sequence, one number a sequence. No bias but
the filter's.

- ``M``, the Mamba-2 mixer (arXiv:2405.21060), H = ``mamba_num_heads``
  heads of P = ``mamba_head_dim``, a state of N = ``ssm_state_size``,
  G = ``n_groups``: ``[z, xBC, dt] = x W_in`` (H P, H P + 2 G N, H wide);
  ``xBC = silu(conv(xBC) + b)`` with ``conv`` one causal filter of
  ``conv_kernel`` taps a channel (``c[t] = sum_j w[:, j] z[t - 3 + j]``,
  zeros before a sequence's start); ``xBC = [x, B, C]``, ``x`` in H heads
  of P, ``B`` and ``C`` in G groups of N, head h reading group h // (H /
  G); ``dt = softplus(dt + dt_bias)`` a head, ``A = -exp(A_log)``; a state
  ``S`` (N x P) a head, zero at a sequence's start,
  ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``,
  ``y_t = C_t^T S_t + D x_t``; then ``W_out rmsnorm_g(y * silu(z))``, the
  norm over each of the G groups of H P / G channels with one weight of
  H P. Here the recurrence runs TOKEN BY TOKEN (``lax.scan`` over T,
  ``jax.checkpoint`` round blocks of ``REF_TOKEN_BLOCK`` tokens so that
  its gradient fits).
- ``*``, attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``, no positions,
  causal ``softmax(q . k / sqrt(head_dim)) v`` in float32, dense, a block
  of queries at a time.
- ``E``, the expert layer (arXiv:2412.19437's router): ``s =
  sigmoid(x W_r)`` over all the published experts in float32; the
  ``num_experts_per_tok`` largest ``s + b`` (``b`` the selection bias:
  float32, no gradient, no update; ``n_group`` 1: no group limit) are
  chosen; weights ``s_e / (sum of the chosen s + 1e-20)`` times
  ``routed_scaling_factor``; the experts held here, each ``down(relu(up
  x)^2)`` of ``moe_intermediate_size``, add their weighted outputs,
  experts held elsewhere add nothing; one shared expert of the same form
  and ``moe_shared_expert_intermediate_size`` on every token,
  unweighted.

``sizes["planted_fault"]`` (no configuration has it; ``benchmark/
calibrate_faults.py`` and the tests set it) plants one fault in this
reference, which is then put in the program's place: ``decay_one`` (A
= 0: the state never forgets), ``no_skip`` (the ``D x`` term left out),
``groups_by_modulo`` (head h reads group h % G: every distribution kept,
so the comparison of norms cannot see it and ``block_witness`` must),
``relu_not_squared`` (the experts' ``relu(u)`` for ``relu(u)^2``).

Parameter names are the net's attribute paths (``layers.1.moe.w_up``).
The experts' matrices are stacked ``(held, in, out)``; every other
matrix is ``(out, in)``; the filter is ``(channels, taps)``. The Mamba
mixer holds its leaves as its own (``layers.0.mamba.in_proj_weight``).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.seeding import seed_key

INIT_STD = 0.02
# the selection bias has no update rule in the config; DeepSeek-V3's
# auxiliary-loss-free rule (arXiv:2412.19437, s.2.1.2) is the one that
# trains it: ``BALANCE_STEPS`` turns of ``b += BALANCE_SPEED * sign(mean
# load - load)`` on ``BALANCE_TOKENS`` tokens of the seed's own, each
# expert's mean score centred first (``assumed.expert_bias``)
BALANCE_TOKENS = 2048
BALANCE_STEPS = 100
BALANCE_SPEED = 1e-3
# the filter and its bias: PyTorch's Conv1d default at a fan-in of 4
# taps, U(-1/2, 1/2), which the source's initialisation leaves in place
# (``assumed.initializer``)
FILTER_BOUND = 0.5
# ``D`` is drawn N(1, D_STD) rather than shipped as ones
D_STD = 0.5
ROUTER_EPS = 1e-20
REF_QUERY_BLOCK = 1024  # queries a block in the reference's attention
REF_TOKEN_BLOCK = 64    # tokens a checkpointed block of its recurrence
FAULTS = ("decay_one", "no_skip", "groups_by_modulo", "relu_not_squared")


# ---------------------------------------------------------------------------
# the layers, from the sizes alone
# ---------------------------------------------------------------------------

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


def layer_plan(sizes):
    """One dict a layer: its index and its block (``mamba``, ``attn`` or
    ``moe``)."""
    return [{"i": i, "block": KINDS[ch]}
            for i, ch in enumerate(sizes["hybrid_override_pattern"])]


def experts_held(sizes):
    """``(first id, number held, number the router scores)``."""
    dep = sizes["deployment"]
    start, stop = dep["experts_held"]
    if stop - start != sizes["n_routed_experts"]:
        raise ValueError("n_routed_experts must count the experts held")
    return start, stop - start, dep["num_experts_published"]


def _mamba_widths(sizes):
    """``(H, P, N, G, d_inner, conv channels)``."""
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    n, g = sizes["ssm_state_size"], sizes["n_groups"]
    return h, p, n, g, h * p, h * p + 2 * g * n


def param_shapes(sizes):
    """name -> (shape, kind) for every leaf; kind ``matrix`` (bfloat16
    under the policy), ``filter`` (the Mamba filter and its bias,
    bfloat16), ``router`` (float32), ``bias`` (the selection bias:
    float32, never trained), ``rate`` / ``dt`` / ``skip`` (``A_log``,
    ``dt_bias``, ``D``: float32) or ``ones`` (a norm's weight,
    float32)."""
    c, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    fe = sizes["moe_intermediate_size"]
    fs = sizes["moe_shared_expert_intermediate_size"]
    mh, _, _, _, inner, width = _mamba_widths(sizes)
    _, held, routed = experts_held(sizes)
    shapes = {"embed.weight": ((v, c), "matrix"),
              "norm.weight": ((c,), "ones"),
              "head.weight": ((v, c), "matrix")}
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}"
        shapes[f"{pre}.norm.weight"] = ((c,), "ones")
        if layer["block"] == "mamba":
            m = f"{pre}.mamba."
            shapes[m + "in_proj_weight"] = ((inner + width + mh, c), "matrix")
            shapes[m + "conv_weight"] = ((width, sizes["conv_kernel"]),
                                         "filter")
            shapes[m + "conv_bias"] = ((width,), "filter")
            shapes[m + "dt_bias"] = ((mh,), "dt")
            shapes[m + "A_log"] = ((mh,), "rate")
            shapes[m + "D"] = ((mh,), "skip")
            shapes[m + "norm_weight"] = ((inner,), "ones")
            shapes[m + "out_proj_weight"] = ((c, inner), "matrix")
        elif layer["block"] == "attn":
            for name, cout, cin in (("q_proj", heads * d, c),
                                    ("k_proj", kv * d, c),
                                    ("v_proj", kv * d, c),
                                    ("o_proj", c, heads * d)):
                shapes[f"{pre}.attn.{name}.weight"] = ((cout, cin), "matrix")
        else:
            shapes[f"{pre}.moe.router_weight"] = ((routed, c), "router")
            shapes[f"{pre}.moe.expert_bias"] = ((routed,), "bias")
            shapes[f"{pre}.moe.w_up"] = ((held, c, fe), "matrix")
            shapes[f"{pre}.moe.w_down"] = ((held, fe, c), "matrix")
            shapes[f"{pre}.moe.shared.up_proj.weight"] = ((fs, c), "matrix")
            shapes[f"{pre}.moe.shared.down_proj.weight"] = ((c, fs),
                                                            "matrix")
    return shapes


F32_LEAVES = ("norm.weight", "norm_weight", "router_weight", "expert_bias",
              "A_log", "dt_bias", ".D")


def param_dtype(name, policy):
    if policy == "f32":
        return jnp.float32
    if policy != "bf16_norm_router_f32":
        raise ValueError(f"nemotron_h: unknown dtype policy {policy!r}")
    return jnp.float32 if name.endswith(F32_LEAVES) else jnp.bfloat16


def is_state(name):
    """Names among the weights that are no trained leaf: the selection
    bias, held fixed through a step."""
    return name.endswith("expert_bias")


def _act_bytes(sizes):
    return 4 if sizes["dtype_policy"] == "f32" else 2


def _count(sizes, block):
    return sum(1 for layer in layer_plan(sizes) if layer["block"] == block)


def attention_products(sizes, traffic, windowed):
    """``(flops, bytes)`` a training step needs for the two attention
    products of the attention layers: two FLOPs a multiply-add over the
    causal half, forward once and backward twice; bytes the least HBM
    traffic in the activations' bytes: q, k, v and the result once each
    pass. The family has no windowed layer: nothing for ``windowed``
    true."""
    if windowed:
        return 0.0, 0.0
    b, t = traffic["batch"], traffic["seq"]
    h, d = sizes["num_attention_heads"], sizes["head_dim"]
    layers = _count(sizes, "attn")
    return (float(layers * 3 * 2 * b * h * (t * (t + 1) // 2) * 2 * d),
            float(layers * 3 * _act_bytes(sizes) * b * t * h * 4 * d))


def expert_products(sizes, rows):
    """``(flops, bytes)`` a training step needs for one expert layer's
    two grouped products (up, down) over ``rows`` routed rows: forward
    once and backward twice; bytes: the rows in and out of each product
    and the held experts' matrices once a pass."""
    c, fe = sizes["hidden_size"], sizes["moe_intermediate_size"]
    _, held, _ = experts_held(sizes)
    flops = 3 * 2 * 2 * rows * c * fe
    nbytes = 3 * _act_bytes(sizes) * (2 * rows * (c + fe)
                                      + 2 * held * c * fe)
    return float(flops), float(nbytes)


def expected_rows(sizes, traffic):
    """Rows an expert layer here gets a step under uniform routing."""
    _, held, routed = experts_held(sizes)
    return traffic["batch"] * traffic["seq"] \
        * sizes["num_experts_per_tok"] * held / routed


def mamba_block_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the Mamba mixers
    taken whole, each as ONE unit: its two projections (in, out) forward
    once and backward twice; bytes: the mixer's input, its output and
    the two matrices once a pass. Whatever the compiler fuses inside the
    mixer, and whatever implements the scan, it cannot do less."""
    c = sizes["hidden_size"]
    mh, _, _, _, inner, width = _mamba_widths(sizes)
    tok = traffic["batch"] * traffic["seq"]
    weights = c * (inner + width + mh) + inner * c
    layers = _count(sizes, "mamba")
    return (float(layers * 3 * 2 * tok * weights),
            float(layers * 3 * _act_bytes(sizes) * (2 * tok * c + weights)))


def ssd_scan_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the SSD alone,
    whatever implements it: the chunk algebra's multiply-adds at the
    configuration's ``chunk_size`` L a token (``C B^T`` a group, L N G;
    the decayed scores by the inputs, L H P; the chunk's state, H P N;
    its read by ``C``, H P N), forward once and backward twice, two FLOPs
    each; bytes: x, B, C in the activations' bytes and ``dt`` in float32
    read and y written once forward; they and ``dy`` read and four
    gradients written once backward."""
    h, p, n, g, _, _ = _mamba_widths(sizes)
    tok = traffic["batch"] * traffic["seq"] * _count(sizes, "mamba")
    macs = sizes["chunk_size"] * (n * g + h * p) + 2 * h * p * n
    act = _act_bytes(sizes)
    operands = (h * p + 2 * g * n) * act + 4 * h
    return (float(tok * 3 * 2 * macs),
            float(tok * (operands + h * p * act               # forward
                         + 2 * operands + h * p * act)))      # backward


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add,
    forward once and backward twice: per token the projections, the
    shared expert, the router and the head; per sequence the two
    attention products over the causal half; the SSD's chunk algebra
    (``ssd_scan_work``, a row of its own a layer); per routed row the two
    grouped products, at the rows uniform routing sends here. Bytes as
    in ``bert.matrix_layers``, in the activations' bytes. Nothing is
    counted for recomputation, for the filter, norms, softmax, sorting,
    gathering or the update."""
    c, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    mh, _, _, _, inner, width = _mamba_widths(sizes)
    tok, nbytes = traffic["batch"] * traffic["seq"], _act_bytes(sizes)
    _, _, routed = experts_held(sizes)
    fs = sizes["moe_shared_expert_intermediate_size"]

    def product(name, cin, cout):
        return (name, float(3 * 2 * tok * cin * cout),
                float(3 * nbytes * (tok * (cin + cout) + cin * cout)))

    out = []
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}"
        if layer["block"] == "mamba":
            flops, moved = ssd_scan_work(sizes, traffic)
            n = _count(sizes, "mamba")
            out += [product(f"{pre}.mamba.in_proj", c, inner + width + mh),
                    (f"{pre}.mamba.scan", flops / n, moved / n),
                    product(f"{pre}.mamba.out_proj", inner, c)]
        elif layer["block"] == "attn":
            flops, moved = attention_products(sizes, traffic, False)
            n = _count(sizes, "attn")
            out += [product(f"{pre}.attn.q_proj", c, heads * d),
                    product(f"{pre}.attn.kv_proj", c, 2 * kv * d),
                    (f"{pre}.attn.products", flops / n, moved / n),
                    product(f"{pre}.attn.o_proj", heads * d, c)]
        else:
            out += [product(f"{pre}.moe.router", c, routed),
                    (f"{pre}.moe.experts",)
                    + expert_products(sizes, expected_rows(sizes, traffic)),
                    product(f"{pre}.moe.shared.up_proj", c, fs),
                    product(f"{pre}.moe.shared.down_proj", fs, c)]
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

def _draw(kind, key, shape, sizes):
    """One leaf of ``kind`` in float32 (``assumed.initializer``)."""
    if kind in ("matrix", "router"):
        return INIT_STD * jax.random.normal(key, shape)
    if kind == "filter":
        return jax.random.uniform(key, shape, minval=-FILTER_BOUND,
                                  maxval=FILTER_BOUND)
    if kind == "skip":
        return 1.0 + D_STD * jax.random.normal(key, shape)
    if kind == "rate":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    # the step size log-uniform in [time_step_min, time_step_max], floored
    # at time_step_floor, through the inverse of the softplus
    lo, hi = math.log(sizes["time_step_min"]), math.log(sizes["time_step_max"])
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, minval=lo,
                                                maxval=hi)),
                     sizes["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def _balanced_biases(sizes, leaves, tokens):
    """Every expert layer's selection bias as the auxiliary-loss-free
    rule leaves it on ``tokens`` (1, T): layer after layer, the plain
    forward up to the layer (its lower layers' biases balanced
    already), each expert's mean score over the tokens centred, then
    ``BALANCE_STEPS`` turns of the rule, the layer's input held. On
    random weights every token's normed state shares one direction, so
    the drawn router alone sends most rows to a few experts (at the
    cut's widths on 1024 tokens the fullest expert reads 2.5-6.6 times
    the mean, and the rows that reach experts 0-7 vary with the seed
    from 0.7 to 1.9 times their share); balanced, 1.04-1.10 and 0.97-1.01.
    The cell's expert layers then see the rows a step that the
    deployment's balanced routing sends them."""
    from benchmark import correctness
    p = {n: v.astype(jnp.float32) for n, v in leaves.items()}
    k, routed = sizes["num_experts_per_tok"], experts_held(sizes)[2]
    h = p["embed.weight"][tokens]
    for layer in layer_plan(sizes):
        pre = f"layers.{layer['i']}."
        own = _own(p, pre)
        if layer["block"] == "moe":
            x = _rms(h, own["norm.weight"], sizes["layer_norm_epsilon"])
            s = jax.nn.sigmoid(x.reshape(-1, x.shape[-1])
                               @ own["moe.router_weight"].T)

            def turn(b, _, s=s):
                _, top = jax.lax.top_k(s + b, k)
                load = jnp.zeros(routed).at[top.ravel()].add(1.0)
                return b + BALANCE_SPEED * jnp.sign(jnp.mean(load) - load), \
                    None

            mean = jnp.mean(s, axis=0)
            p[pre + "moe.expert_bias"] = own["moe.expert_bias"] = \
                jax.lax.scan(turn, jnp.mean(mean) - mean, None,
                             BALANCE_STEPS)[0]
        h = _layer(sizes, layer, h, own, correctness.Rounding)[0]
    return {n: p[n] for n in leaves if is_state(n)}


def make_weights(sizes, policy, seed):
    """All leaves made in one jitted call on the device, in the type
    they are trained in, and handed over as HOST arrays (as
    ``ling_hybrid.make_weights``: the harness keeps them beside the
    reference's three steps): every matrix and router N(0, 0.02), the
    filter and its bias U(-1/2, 1/2), ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of a log-uniform step size, ``D``
    N(1, ``D_STD``), norm weights one; the selection biases balanced
    on ``BALANCE_TOKENS`` tokens drawn uniformly from the slice
    (``_balanced_biases``)."""
    build = _weights_program(json.dumps(sizes, sort_keys=True), policy)
    return jax.device_get(build(seed_key(seed, 0)))


@functools.lru_cache(maxsize=None)
def _weights_program(sizes_json, policy):
    """``make_weights``' one jitted call, traced once a configuration: a
    seed is its argument."""
    sizes = json.loads(sizes_json)
    shapes = param_shapes(sizes)

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            dt = param_dtype(name, policy)
            if kind == "ones":
                leaves[name] = jnp.ones(shape, dt)
            elif kind == "bias":
                leaves[name] = jnp.zeros(shape, dt)
            else:
                leaves[name] = _draw(kind, jax.random.fold_in(key, i), shape,
                                     sizes).astype(dt)
        tokens = jax.random.randint(jax.random.fold_in(key, len(shapes)),
                                    (1, BALANCE_TOKENS), 0,
                                    sizes["vocab_size"])
        with jax.default_matmul_precision("highest"):
            balanced = _balanced_biases(sizes, leaves, tokens)
        return {**leaves, **{n: b.astype(leaves[n].dtype)
                             for n, b in balanced.items()}}

    return jax.jit(build)


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


def _load(block, weights, prefix, policy):
    """``block``'s parameters set to ``weights[prefix + name]`` in the
    dtype the policy stores them in (copies: the fused step donates what
    the net holds)."""
    from mxnet_tpu.ndarray.ndarray import _wrap
    params = block._collect_params_with_prefix()
    mine = {n[len(prefix):] for n in weights if n.startswith(prefix)}
    if set(params) != mine:
        raise RuntimeError("the net's parameters and the benchmark's "
                           "differ: " + str(sorted(set(params) ^ mine)[:6]))
    for name, p in params.items():
        dt = str(jnp.dtype(param_dtype(prefix + name, policy)))
        if str(p.data().dtype) != dt:
            p.cast(dt)
        p.set_data(_wrap(jnp.array(weights[prefix + name], copy=True)))


def build_program(sizes, policy, weights, ctx, sample_x):
    """``models.NemotronHLM.from_config`` on ``ctx`` holding ``weights``,
    and its loss. One untimed eager forward on the first batch follows:
    that is where the expert layers fill their telemetry gauges (rows
    routed here, the fullest expert over the mean, the share of tokens
    whose choice the bias changed)."""
    from mxnet_tpu import autograd, gluon, models
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = models.NemotronHLM.from_config(sizes)
    net.initialize(ctx=ctx)
    _load(net, weights, "", policy)
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


def _attention(qh, kh, vh, q):
    """Dense causal attention, one query head and one block of queries
    at a time, a query head reading key/value head ``h // (Hq / Hkv)``.
    ``qh`` (B, Hq, T, D); ``kh``, ``vh`` (B, Hkv, T, D)."""
    b, h, t, d = qh.shape
    if b == 0:  # the fault that leaves half of a batch of one out
        return jnp.zeros(qh.shape, qh.dtype)
    per_kv = h // kh.shape[1]
    bq = REF_QUERY_BLOCK if t % REF_QUERY_BLOCK == 0 else t
    nq = t // bq
    blocks = qh.reshape(b * h * nq, bq, d)
    kf, vf = kh.reshape(-1, t, d), vh.reshape(-1, t, d)
    kpos = jnp.arange(t)[None, :]

    def one(args):
        block, item = args
        head, start = item // nq, (item % nq) * bq
        kv = (head // h) * kh.shape[1] + (head % h) // per_kv
        s = q.out(q.inp(block) @ q.inp(kf[kv]).T) / math.sqrt(d)
        seen = kpos <= start + jnp.arange(bq)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return q.out(q.inp(p) @ q.inp(vf[kv]))

    out = jax.lax.map(jax.checkpoint(one),
                      (blocks, jnp.arange(b * h * nq)))
    return out.reshape(b, h, t, d)


def _conv_silu(x, w, bias):
    """``silu`` of one causal filter a channel plus its bias: the taps as
    shifted adds, zeros before a sequence's start. ``x`` (B, T, C); ``w``
    (C, taps)."""
    t, taps = x.shape[1], w.shape[1]
    z = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * z[:, j:j + t] for j in range(taps))
                       + bias)


def ssd_recurrence(x, dt, a, b, c, group):
    """The SSD token by token. ``x`` (B, T, H, P), ``dt`` (B, T, H), ``a``
    (H,), ``b``, ``c`` (B, T, G, N), ``group`` (H,) the group each head
    reads -> (B, T, H, P), float32 state; a block of ``REF_TOKEN_BLOCK``
    tokens is checkpointed."""
    bsz, t, h, p = x.shape
    if bsz == 0:
        return jnp.zeros_like(x)
    block = REF_TOKEN_BLOCK if t % REF_TOKEN_BLOCK == 0 else t

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = b_t[:, group], c_t[:, group]            # (B, H, N)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state)

    def tokens(state, xs):
        # a token keeps the state it entered with and nothing else
        return jax.lax.scan(jax.checkpoint(token), state, xs)

    xs = [jnp.moveaxis(v, 1, 0).reshape((t // block, block) + v.shape[:1]
                                        + v.shape[2:])
          for v in (x, dt, b, c)]
    _, y = jax.lax.scan(jax.checkpoint(tokens),
                        jnp.zeros((bsz, h, b.shape[-1], p), jnp.float32), xs)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba_mixer(sizes, x, p, q):
    bsz, t, _ = x.shape
    h, hp, n, g, inner, width = _mamba_widths(sizes)
    f32 = jnp.float32
    fault = sizes.get("planted_fault")
    proj = q.out(q.inp(x) @ q.inp(p["in_proj_weight"]).T)
    z, xbc, dt = proj[..., :inner], proj[..., inner:inner + width], \
        proj[..., inner + width:]
    xbc = q.act(_conv_silu(xbc.astype(f32), p["conv_weight"].astype(f32),
                           p["conv_bias"].astype(f32)))
    xs = xbc[..., :inner].reshape(bsz, t, h, hp).astype(f32)
    bm = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n).astype(f32)
    cm = xbc[..., inner + g * n:].reshape(bsz, t, g, n).astype(f32)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    a = -jnp.exp(p["A_log"].astype(f32))
    if fault == "decay_one":
        a = jnp.zeros_like(a)
    group = np.arange(h) % g if fault == "groups_by_modulo" \
        else np.arange(h) // (h // g)
    y = ssd_recurrence(xs, dt, a, bm, cm, group)
    if fault != "no_skip":
        y = y + p["D"].astype(f32)[:, None] * xs
    y = q.act(y.astype(x.dtype)).astype(f32).reshape(bsz, t, inner) \
        * jax.nn.silu(z.astype(f32))
    y = _rms(y.reshape(bsz, t, g, inner // g), 1.0,
             sizes["layer_norm_epsilon"]).reshape(bsz, t, inner) \
        * p["norm_weight"].astype(f32)
    y = q.act(y.astype(x.dtype))
    return q.out(q.inp(y) @ q.inp(p["out_proj_weight"]).T)


def _attention_block(sizes, x, p, q):
    b, t, _ = x.shape
    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])

    def dense(name, a=x):
        return q.out(q.inp(a) @ q.inp(p[f"{name}.weight"]).T)

    def split(a, n):
        return q.act(a).reshape(b, t, n, d).transpose(0, 2, 1, 3)

    o = _attention(split(dense("q_proj"), heads), split(dense("k_proj"), kv),
                   split(dense("v_proj"), kv), q)
    # every width named: a batch of none (``half_batch`` of one sequence)
    # leaves a -1 nothing to divide
    return dense("o_proj", q.act(o.transpose(0, 2, 1, 3).reshape(
        b, t, heads * d)))


def _relu2(u, sizes):
    r = jax.nn.relu(u)
    return r if sizes.get("planted_fault") == "relu_not_squared" else r * r


def _relu2_ffn(sizes, x, up, down, q):
    """``up`` (in, out), ``down`` (out, in) as (F, C)."""
    hidden = _relu2(q.out(q.inp(x) @ q.inp(up)), sizes)
    return q.out(q.inp(q.act(hidden)) @ q.inp(down))


def routing(sizes, x, router_w, bias):
    """``(weights, expert ids)``, each (N, k), of the tokens ``x`` in
    float32: sigmoid scores over all the router's outputs; the k largest
    with the bias added; the unbiased scores of those renormalised, times
    the scaling factor."""
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

    def one(acc, expert):
        # the expert's matrices come as the scan's inputs: closed over,
        # their gradients would be summed into float32 buffers of all
        # the held experts' size, which the compiler allocates early
        e, up, down = expert
        w = jnp.sum(jnp.where(ids == start + e, weights, 0.0), axis=-1)
        y = _relu2_ffn(sizes, x, up, down, q)
        return acc + w[:, None].astype(x.dtype) * y, None

    acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          (jnp.arange(count), p["w_up"], p["w_down"]))
    return acc, ids


def _shared_expert(sizes, x, p, q):
    return _relu2_ffn(sizes, x, p["shared.up_proj.weight"].T,
                      p["shared.down_proj.weight"].T, q)


def _own(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items()
            if k.startswith(prefix)}


def _layer(sizes, layer, h, p, q):
    """``(h + block(norm(h)), the expert ids the router chose (N, k) or
    None)`` from the layer's leaves ``p``."""
    b, t, c = h.shape
    x = q.act(_rms(h, p["norm.weight"], sizes["layer_norm_epsilon"]))
    if layer["block"] == "mamba":
        return h + _mamba_mixer(sizes, x, _own(p, "mamba."), q), None
    if layer["block"] == "attn":
        return h + _attention_block(sizes, x, _own(p, "attn."), q), None
    flat, own = x.reshape(b * t, c), _own(p, "moe.")
    routed, ids = _experts(sizes, flat, own, q)
    return h + (routed + _shared_expert(sizes, flat, own, q)
                ).reshape(b, t, c), ids


def _stored(sizes, name, value):
    """``value`` in the dtype its leaf is stored in (as
    ``ling_hybrid._stored``: what the backward pass keeps of a layer is
    the stored leaf, never a float32 copy of every matrix)."""
    return value.astype(param_dtype(name, sizes["dtype_policy"]))


def _kept_whole(fn):
    """``fn(h, own) -> h`` rematerialised in the backward pass, which
    keeps ``h`` and ``own`` alone and may not start its recomputation
    before the block's cotangent has arrived (the barrier)."""
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

        if expert_ids is not None and layer["block"] == "moe":
            expert_ids[pre[:-1]] = block(h, own)[1]
        # one layer's activations at a time are kept for backward
        h = _kept_whole(lambda h, own, block=block: block(h, own)[0])(
            h, own)
    return q.act(_rms(h, params["norm.weight"], sizes["layer_norm_epsilon"]))


def reference_logits(sizes, params, x, q, expert_ids=None):
    """Float32 logits (B, T, V) of the network on token ids ``x``;
    ``expert_ids``, a dict, is filled with each expert layer's chosen
    expert ids under the layer's name (``layers.1``)."""
    h = _hidden(sizes, params, x, q, expert_ids)
    return q.out(q.inp(h) @ q.inp(params["head.weight"]).T
                 ).astype(jnp.float32)


def reference_loss(sizes, params, x, y, q, key):
    """Per-sequence mean cross-entropy of the network, and no state.
    ``params`` hold every leaf in the dtype to compute in; ``q.inp`` is
    called on every operand of a matrix product, ``q.out`` on its result
    and ``q.act`` on every array kept between products
    (``correctness.Rounding``: nothing for the reference). The router's
    product and scores, the filter's arithmetic, the step sizes, the
    decay and the recurrence's state stay in float32 under every
    rounding, as the policy keeps them. The step's ``key`` goes unused:
    nothing here is drawn. The head and the loss go a block of
    ``REF_QUERY_BLOCK`` positions at a time."""
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
    """``{"mamba_block_gap": ..}``: the program's Mamba mixer
    (``models.NemotronHMamba`` on ``ctx`` holding the seed's leaves of
    the cut's first mixer) against ``_mamba_mixer`` in float32, on the
    layer's normed embedding of the first batch's tokens; the distance
    between the two outputs over the reference's norm. What the step's
    comparison of norms cannot see of the mixer (a head reading another
    group's B and C, a wrong order of the projection's parts) moves
    every output here."""
    from mxnet_tpu import autograd, models
    from mxnet_tpu.ndarray.ndarray import _wrap
    from benchmark import correctness

    pre = next(f"layers.{layer['i']}." for layer in layer_plan(sizes)
               if layer["block"] == "mamba")
    own = _own(weights, pre + "mamba.")
    h, hp, n, g, _, _ = _mamba_widths(sizes)
    block = models.NemotronHMamba(sizes["hidden_size"], h, hp, n, g,
                                  sizes["conv_kernel"],
                                  sizes["layer_norm_epsilon"])
    block.initialize(ctx=ctx)
    _load(block, weights, pre + "mamba.", policy)
    embed = jnp.asarray(weights["embed.weight"])
    x = _rms(embed[batches[0][0]].astype(jnp.float32),
             jnp.asarray(weights[pre + "norm.weight"]),
             sizes["layer_norm_epsilon"]).astype(embed.dtype)
    with autograd.pause():
        got = block(_wrap(x))._data.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x, own: _mamba_mixer(
            sizes, x, own, correctness.Rounding))(
                x.astype(jnp.float32),
                {k: jnp.asarray(w, jnp.float32) for k, w in own.items()})
    return {"mamba_block_gap": float(jnp.linalg.norm(got - ref)
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
