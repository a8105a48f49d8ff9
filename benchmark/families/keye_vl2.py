"""Family ``keye_vl2``: the Keye-VL-2.0 language model
(``Kwai-Keye/Keye-VL-2.0-30B-A3B``, ``model_type: KeyeVL2``) as
``mxnet_tpu.models.KeyeLM`` builds it, trained on a per-position
cross-entropy, on ONE chip's share of a stated deployment (the
configuration's ``deployment``: which of the routed experts and which
slice of the vocabulary are held here).

Found by the family's name: the weights (one jitted call from the seed),
the program side (the Gluon net holding them, its loss, its batches),
the plain reference in straightforward ``jax.numpy`` (it imports nothing
of ``mxnet_tpu``), the matrix work a step needs from the shapes at two
FLOPs a multiply-add, and what the per-layer readers of this family's
cell compute from (``expert_products``, ``indexer_work``,
``sparse_attention_work``).

The layer equations (the configuration's ``assumed`` lists what the
source leaves open). Every layer is ``h = x + attn(rmsnorm(x))``, then
``h + moe(rmsnorm(h))``; RMSNorm in float32 with a weight, eps
``rms_norm_eps``; one RMSNorm after the last layer; an untied head; the
loss is the mean cross-entropy over every position of a sequence, one
number a sequence. No bias but the indexer's key norm's.

- attention, ``n`` the normed input: ``q = n W_q`` (H heads of D), ``k =
  n W_k``, ``v = n W_v`` (G heads of D); an RMSNorm over each head of q
  and of k; rotate-half rotary over the whole head at ``rope_theta``,
  text positions.
- the indexer (DeepSeek-V3.2's), on ``stop_gradient(n)``: ``qi = n W_qi``
  (Hi heads of Di), ``ki = LayerNorm(n W_ki)`` (one head, a weight and a
  bias, eps ``rms_norm_eps``), ``w = n W_w / sqrt(Hi)``; rotate-half
  rotary over the first Di / 2 dimensions of ``qi`` and ``ki``; ``I[t,
  s] = Di^-1/2 sum_j w[t, j] relu(qi[t, j] . ki[s])`` for ``s <= t``;
  ``S_t`` the ``min(t + 1, topk)`` keys ``lax.top_k`` gives for
  ``I[t, :t+1]`` (the lower position first among equals), scattered
  into a mask. Here the scores, the selection and the attention go a
  block of ``REF_QUERY_BLOCK`` queries at a time, one head at a time.
- sparse attention: ``o[t, h] = sum_{s in S_t} softmax_s(q[t, h] .
  k[s, h // (H / G)] / sqrt(D)) v[s, h // (H / G)]`` in float32, then
  ``o W_o``.
- the indexer's loss: ``p_t`` the attention's probabilities over
  ``S_t`` summed over the heads over their sum (held fixed); ``L_I =
  sum_t KL(p_t || softmax(I[t, S_t]))`` a sequence, summed over the
  layers; it trains the indexer's leaves alone (its input is stopped,
  ``p_t`` held). It enters the loss as ``L_I - stop_gradient(L_I)``: the
  loss read is the LM loss and its gradient is both losses'.
- the expert layer: softmax over all the published experts' router
  outputs in float32, the ``num_experts_per_tok`` largest (the lower
  index first among equals), renormalised, scaling 1; the experts held
  here, each ``down(silu(gate x) * up x)`` of ``moe_intermediate_size``,
  add their weighted outputs; experts held elsewhere add nothing; no
  shared expert.

``sizes["planted_fault"]`` (no configuration has it; ``benchmark/
calibrate_faults.py`` and the tests set it) plants one fault in this
reference, which is then put in the program's place:
``window_selection`` (``S_t`` the latest ``topk`` keys: every
distribution of an untrained step kept, so ``block_witness`` must see
it), ``indexer_no_relu`` (the scores without the ReLU),
``no_kl_gradient`` (``L_I`` left out: the indexer never learns).

Parameter names are the net's attribute paths (``layers.1.moe.w_up``).
The experts' matrices are stacked ``(held, in, out)``; every other
matrix is ``(out, in)``. The indexer holds its leaves as its own
(``layers.0.attn.indexer.q_proj_weight``).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.seeding import seed_key

INIT_STD = 0.02
REF_QUERY_BLOCK = 1024  # queries a block in the reference's attention
REF_SCORE_BLOCK = 256   # queries a block of its index scores
FAULTS = ("window_selection", "indexer_no_relu", "no_kl_gradient")


# ---------------------------------------------------------------------------
# the shapes, from the sizes alone
# ---------------------------------------------------------------------------

def experts_held(sizes):
    """``(first id, number held, number the router scores)``."""
    dep = sizes["deployment"]
    start, stop = dep["experts_held"]
    if stop - start != sizes["num_experts"] \
            or sizes["num_local_experts"] != sizes["num_experts"]:
        raise ValueError("num_experts and num_local_experts must count the "
                         "experts held")
    return start, stop - start, dep["num_experts_published"]


def _widths(sizes):
    """``(H, G, D, Hi, Di, topk)``."""
    sa = sizes["sa_config"]
    return (sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"], sa["indexer_num_heads"],
            sa["indexer_head_dim"], sa["topk"])


def param_shapes(sizes):
    """name -> (shape, kind) for every leaf; kind ``matrix`` (bfloat16
    under the policy), ``router`` (float32), ``ones`` or ``zeros`` (a
    norm's weight or bias, float32)."""
    c, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv, d, ih, idim, _ = _widths(sizes)
    fe = sizes["moe_intermediate_size"]
    _, held, routed = experts_held(sizes)
    shapes = {"embed.weight": ((v, c), "matrix"),
              "norm.weight": ((c,), "ones"),
              "head.weight": ((v, c), "matrix")}
    for i in range(sizes["num_hidden_layers"]):
        pre = f"layers.{i}."
        for norm in ("attn_norm", "mlp_norm"):
            shapes[pre + norm + ".weight"] = ((c,), "ones")
        for name, cout, cin in (("q_proj", heads * d, c),
                                ("k_proj", kv * d, c),
                                ("v_proj", kv * d, c),
                                ("o_proj", c, heads * d)):
            shapes[f"{pre}attn.{name}.weight"] = ((cout, cin), "matrix")
        for norm in ("q_norm", "k_norm"):
            shapes[f"{pre}attn.{norm}.weight"] = ((d,), "ones")
        ix = pre + "attn.indexer."
        shapes[ix + "q_proj_weight"] = ((ih * idim, c), "matrix")
        shapes[ix + "k_proj_weight"] = ((idim, c), "matrix")
        shapes[ix + "w_proj_weight"] = ((ih, c), "matrix")
        shapes[ix + "k_norm_weight"] = ((idim,), "ones")
        shapes[ix + "k_norm_bias"] = ((idim,), "zeros")
        shapes[pre + "moe.router_weight"] = ((routed, c), "router")
        for name in ("w_gate", "w_up"):
            shapes[f"{pre}moe.{name}"] = ((held, c, fe), "matrix")
        shapes[pre + "moe.w_down"] = ((held, fe, c), "matrix")
    return shapes


F32_LEAVES = ("norm.weight", "norm_weight", "norm_bias", "router_weight")


def param_dtype(name, policy):
    if policy == "f32":
        return jnp.float32
    if policy != "bf16_norm_router_f32":
        raise ValueError(f"keye_vl2: unknown dtype policy {policy!r}")
    return jnp.float32 if name.endswith(F32_LEAVES) else jnp.bfloat16


def is_state(name):
    """No leaf here is anything but trained."""
    return False


def _act_bytes(sizes):
    return 4 if sizes["dtype_policy"] == "f32" else 2


def _pairs(sizes, traffic):
    """``(causal pairs, selected pairs)`` of query and key a step."""
    b, t, topk = traffic["batch"], traffic["seq"], _widths(sizes)[5]
    k = min(t, topk)
    return b * t * (t + 1) // 2, b * (k * (k + 1) // 2 + (t - k) * k)


def indexer_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the indexer's scores,
    whatever implements them: the heads' products ``qi . ki`` (Hi Di
    multiply-adds a pair) over every causal pair forward, and the KL
    loss's two gradients of them (``qi``'s and ``ki``'s) over the
    selected pairs backward, two FLOPs each; bytes: ``qi``, ``ki`` in
    the activations' bytes and ``w`` in float32 read and the selected
    keys' indices (int32) written forward; they read and the three
    gradients written backward."""
    _, _, _, ih, idim, _ = _widths(sizes)
    causal, selected = _pairs(sizes, traffic)
    tok = traffic["batch"] * traffic["seq"]
    layers, act = sizes["num_hidden_layers"], _act_bytes(sizes)
    operands = tok * ((ih * idim + idim) * act + 4 * ih)
    return (float(layers * 2 * ih * idim * (causal + 2 * selected)),
            float(layers * (2 * operands + 2 * 4 * selected
                            + tok * ((ih * idim + idim) * 4 + 4 * ih))))


def sparse_attention_work(sizes, traffic):
    """``(flops, bytes)`` a training step needs for the attention over
    the selected keys alone, whatever implements it: ``q . k`` and the
    probabilities by ``v`` (H D multiply-adds each) over the selected
    pairs, forward once and backward twice, two FLOPs each; bytes: q, k,
    v and o in the activations' bytes and the selected keys' indices
    (int32), once each way."""
    heads, kv, d, _, _, _ = _widths(sizes)
    _, selected = _pairs(sizes, traffic)
    tok = traffic["batch"] * traffic["seq"]
    layers = sizes["num_hidden_layers"]
    per_way = tok * (2 * heads + 2 * kv) * d * _act_bytes(sizes) \
        + 4 * selected
    return (float(layers * 3 * 2 * 2 * heads * d * selected),
            float(layers * 2 * per_way))


def expert_products(sizes, rows):
    """``(flops, bytes)`` a training step needs for one expert layer's
    three grouped products (gate, up, down) over ``rows`` routed rows:
    forward once and backward twice; bytes: the rows in and out of each
    product and the held experts' matrices once a pass."""
    c, fe = sizes["hidden_size"], sizes["moe_intermediate_size"]
    _, held, _ = experts_held(sizes)
    flops = 3 * 2 * 3 * rows * c * fe
    nbytes = 3 * _act_bytes(sizes) * (3 * rows * (c + fe)
                                      + 3 * held * c * fe)
    return float(flops), float(nbytes)


def expected_rows(sizes, traffic):
    """Rows an expert layer here gets a step under uniform routing."""
    _, held, routed = experts_held(sizes)
    return traffic["batch"] * traffic["seq"] \
        * sizes["num_experts_per_tok"] * held / routed


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add,
    forward once and backward twice: per token the attention's and the
    indexer's projections, the router and the head; the indexer's scores
    (``indexer_work``) and the attention over the selected keys
    (``sparse_attention_work``), a row each a layer; per routed row the
    experts' three grouped products, at the rows uniform routing sends
    here. Bytes as in ``bert.matrix_layers``, in the activations' bytes.
    Nothing is counted for recomputation, for the top-k, norms, softmax,
    sorting, gathering or the update."""
    c, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv, d, ih, idim, _ = _widths(sizes)
    tok, nbytes = traffic["batch"] * traffic["seq"], _act_bytes(sizes)
    _, _, routed = experts_held(sizes)
    n = sizes["num_hidden_layers"]
    scores = [x / n for x in indexer_work(sizes, traffic)]
    sparse = [x / n for x in sparse_attention_work(sizes, traffic)]

    def product(name, cin, cout):
        return (name, float(3 * 2 * tok * cin * cout),
                float(3 * nbytes * (tok * (cin + cout) + cin * cout)))

    out = []
    for i in range(n):
        pre = f"layers.{i}."
        out += [product(pre + "attn.q_proj", c, heads * d),
                product(pre + "attn.kv_proj", c, 2 * kv * d),
                product(pre + "attn.indexer.proj", c, ih * idim + idim + ih),
                (pre + "attn.indexer.scores", *scores),
                (pre + "attn.sparse", *sparse),
                product(pre + "attn.o_proj", heads * d, c),
                product(pre + "moe.router", c, routed),
                (pre + "moe.experts",)
                + expert_products(sizes, expected_rows(sizes, traffic))]
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
    """All leaves in one jitted call, in the type they are trained in,
    handed over as HOST arrays (the harness keeps them beside the
    reference's three steps): every matrix and the router N(0, 0.02),
    norm weights one, the indexer's key norm's bias zero."""
    build = _weights_program(json.dumps(sizes, sort_keys=True), policy)
    return jax.device_get(build(seed_key(seed, 0)))


@functools.lru_cache(maxsize=None)
def _weights_program(sizes_json, policy):
    """``make_weights``' one jitted call, traced once a configuration: a
    seed is its argument."""
    shapes = param_shapes(json.loads(sizes_json))

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            dt = param_dtype(name, policy)
            if kind == "ones":
                leaves[name] = jnp.ones(shape, dt)
            elif kind == "zeros":
                leaves[name] = jnp.zeros(shape, dt)
            else:
                leaves[name] = (INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dt)
        return leaves

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
    """``models.KeyeLM.from_config`` on ``ctx`` holding ``weights``, and
    its loss. One untimed eager forward on the first batch follows:
    that is where the expert layers fill their telemetry gauges (rows
    routed here, the fullest expert over the mean)."""
    from mxnet_tpu import autograd, gluon, models
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = models.KeyeLM.from_config(sizes)
    net.initialize(ctx=ctx)
    _load(net, weights, "", policy)
    with autograd.pause():
        net(_wrap(sample_x)).wait_to_read()
    PROGRAM_EXPERT_IDS.clear()
    for i, layer in enumerate(net.layers):
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


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _rope(x, theta, dim):
    """``x`` (B, T, H, D) with its first ``dim`` dimensions turned by the
    position, rotate-half over them."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _indexer(sizes, x, p, q):
    """``(qi (B, T, Hi, Di), ki (B, T, Di), w (B, T, Hi))`` of the normed
    input ``x``, whose gradient stops here."""
    b, t, _ = x.shape
    _, _, _, ih, idim, _ = _widths(sizes)
    theta = sizes["rope_theta"]
    x = jax.lax.stop_gradient(x)
    qi = q.out(q.inp(x) @ q.inp(p["q_proj_weight"]).T)
    qi = q.act(_rope(q.act(qi).reshape(b, t, ih, idim), theta, idim // 2))
    ki = _layer_norm(q.out(q.inp(x) @ q.inp(p["k_proj_weight"]).T),
                     p["k_norm_weight"], p["k_norm_bias"],
                     sizes["rms_norm_eps"])
    ki = q.act(_rope(q.act(ki)[:, :, None], theta, idim // 2)[:, :, 0])
    w = (q.inp(x) @ q.inp(p["w_proj_weight"]).T) / math.sqrt(ih)
    return qi, ki, w.astype(jnp.float32)


def _scores(sizes, qi, ki, w, q):
    """The index scores (B, Q, T) of a block of queries over every key,
    ``REF_SCORE_BLOCK`` queries at a time (the heads' products of a
    block of 1024 over 16384 keys would be 1 GB)."""
    b, nq = qi.shape[:2]
    sub = REF_SCORE_BLOCK if nq % REF_SCORE_BLOCK == 0 else nq

    def part(args):
        qi, w = args
        a = q.out(jnp.einsum("bqjd,bsd->bqjs", q.inp(qi), q.inp(ki)))
        if sizes.get("planted_fault") != "indexer_no_relu":
            a = jax.nn.relu(a)
        return jnp.sum(a.astype(jnp.float32) * w[..., None], axis=2) \
            / math.sqrt(qi.shape[-1])

    def split(a):
        return jnp.moveaxis(a.reshape((b, nq // sub, sub) + a.shape[2:]),
                            1, 0)

    out = jax.lax.map(jax.checkpoint(part), (split(qi), split(w)))
    return jnp.moveaxis(out, 0, 1).reshape(b, nq, -1)


def _selection(sizes, scores, start):
    """The selection (B, Q, T) bool of a block of queries at positions
    ``start`` .. from their scores: the indices ``lax.top_k`` gives over
    the causal keys, scattered into a mask."""
    b, nq, t = scores.shape
    topk = _widths(sizes)[5]
    qpos = start + jnp.arange(nq)[:, None]
    kpos = jnp.arange(t)[None, :]
    causal = kpos <= qpos
    if sizes.get("planted_fault") == "window_selection":
        return jnp.broadcast_to(causal & (kpos > qpos - topk), scores.shape)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, t))
    mask = jnp.zeros((b, nq, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(nq)[None, :, None],
        idx].set(True)
    return mask & causal


def _blocked(t):
    return REF_QUERY_BLOCK if t % REF_QUERY_BLOCK == 0 else t


def _sparse(sizes, qh, kh, vh, qi, ki, w, q):
    """The sparse attention (B, T, H, D) and each sequence's ``L_I``
    (B,), a block of queries at a time: the block's scores and selection,
    then one head at a time its masked softmax over every key; the KL
    target from the heads' probabilities again, out of the gradient's
    way."""
    b, t, heads, d = qh.shape
    if b == 0:  # the fault that leaves half of a batch of one out
        return jnp.zeros_like(qh), jnp.zeros((0,), jnp.float32)
    per_kv = heads // kh.shape[2]
    bq = _blocked(t)

    def probabilities(qb, kh, h, mask):
        s = q.out(jnp.einsum("bqd,bsd->bqs", q.inp(qb[:, :, h]),
                             q.inp(kh[:, :, h // per_kv]))) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)

    def block(j):
        start = j * bq

        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, start, bq, axis=1)

        qb = rows(qh)
        scores = _scores(sizes, rows(qi), ki, rows(w), q)
        mask = jax.lax.stop_gradient(_selection(sizes, scores, start))

        def head(_, h):
            p = probabilities(qb, kh, h, mask)
            return None, q.out(jnp.einsum("bqs,bsd->bqd", q.inp(p),
                                          q.inp(vh[:, :, h // per_kv])))

        _, o = jax.lax.scan(jax.checkpoint(head), None, jnp.arange(heads))
        held = jax.lax.stop_gradient((qb, kh))
        target = jax.lax.fori_loop(
            0, heads, lambda h, acc: acc + probabilities(*held, h, mask),
            jnp.zeros(scores.shape, jnp.float32)) / heads
        logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        kl = jnp.where(mask & (target > 0),
                       target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                 - logq), 0.0)
        return jnp.moveaxis(o, 0, 2), jnp.sum(kl, axis=(1, 2))

    o, kl = jax.lax.map(jax.checkpoint(block), jnp.arange(t // bq))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads, d)
    return o, jnp.sum(kl, axis=0)


def _attention_block(sizes, x, p, q):
    """``(o_proj of the sparse attention (B, T, C), L_I (B,))`` of the
    normed input ``x`` from the block's leaves ``p`` (``attn.`` off)."""
    b, t, _ = x.shape
    heads, kv, d, _, _, _ = _widths(sizes)
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]

    def dense(name, a=x):
        return q.out(q.inp(a) @ q.inp(p[f"{name}.weight"]).T)

    def heads_of(name, n):
        normed = _rms(dense(name + "_proj").reshape(b, t, n, d),
                      p[name + "_norm.weight"], eps)
        return q.act(_rope(q.act(normed), theta, d))

    qh, kh = heads_of("q", heads), heads_of("k", kv)
    vh = q.act(dense("v_proj").reshape(b, t, kv, d))
    qi, ki, w = _indexer(sizes, x, _own(p, "indexer."), q)
    o, kl = _sparse(sizes, qh, kh, vh, qi, ki, w, q)
    # every width named: a batch of none (``half_batch`` of one sequence)
    # leaves a -1 nothing to divide
    return dense("o_proj", q.act(o.reshape(b, t, heads * d))), kl


def _gated_ffn(x, gate, up, down, q):
    """``gate`` / ``up`` (in, out), ``down`` (out, in) as (F, C)."""
    hidden = jax.nn.silu(q.out(q.inp(x) @ q.inp(gate))) \
        * q.out(q.inp(x) @ q.inp(up))
    return q.out(q.inp(q.act(hidden)) @ q.inp(down))


def routing(sizes, x, router_w):
    """``(weights, expert ids)``, each (N, k), of the tokens ``x`` in
    float32: softmax over all the router's outputs, the k largest,
    renormalised over the k (scaling 1)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32).T,
                     precision="highest")
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_i


def _experts(sizes, x, p, q, held=None):
    """The held experts' part of the routed FFN for tokens ``x``
    (N, C): a loop over the experts, each on every token under a mask of
    its routing weight; no sort, no grouping. ``held``: (first id,
    count), the configuration's by default."""
    start, count = held or experts_held(sizes)[:2]
    weights, ids = routing(sizes, x, p["router_weight"])

    def one(acc, expert):
        # the expert's matrices come as the scan's inputs: closed over,
        # their gradients would be summed into float32 buffers of all
        # the held experts' size
        e, gate, up, down = expert
        wt = jnp.sum(jnp.where(ids == start + e, weights, 0.0), axis=-1)
        y = _gated_ffn(x, gate, up, down, q)
        return acc + wt[:, None].astype(x.dtype) * y, None

    acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          (jnp.arange(count), p["w_gate"], p["w_up"],
                           p["w_down"]))
    return acc, ids


def _own(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items()
            if k.startswith(prefix)}


def _layer(sizes, h, p, q):
    """``(the layer's output, L_I (B,), the expert ids (N, k))`` from the
    layer's leaves ``p``."""
    b, t, c = h.shape
    eps = sizes["rms_norm_eps"]
    attn, kl = _attention_block(sizes, q.act(_rms(h, p["attn_norm.weight"],
                                                   eps)),
                                _own(p, "attn."), q)
    h = h + attn
    x = q.act(_rms(h, p["mlp_norm.weight"], eps))
    routed, ids = _experts(sizes, x.reshape(b * t, c), _own(p, "moe."), q)
    return h + routed.reshape(b, t, c), kl, ids


def _stored(sizes, name, value):
    """``value`` in the dtype its leaf is stored in: what the backward
    pass keeps of a layer is the stored leaf, never a float32 copy of
    every matrix."""
    return value.astype(param_dtype(name, sizes["dtype_policy"]))


def _kept_whole(fn):
    """``fn(h, own) -> (h, kl)`` rematerialised in the backward pass,
    which keeps ``h`` and ``own`` alone and may not start its
    recomputation before the block's cotangents have arrived (the
    barrier)."""
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
    """``(the final norm's output (B, T, C), L_I over the layers (B,))``
    on token ids ``x``."""
    compute = params["embed.weight"].dtype
    h = q.act(_stored(sizes, "embed.weight",
                      params["embed.weight"])[x].astype(compute))
    kl = 0.0
    for i in range(sizes["num_hidden_layers"]):
        pre = f"layers.{i}."
        own = {k[len(pre):]: _stored(sizes, k, v)
               for k, v in params.items() if k.startswith(pre)}
        kept = {k: params[pre + k].dtype for k in own}

        def block(h, own, kept=kept):
            return _layer(sizes, h, {k: v.astype(kept[k])
                                     for k, v in own.items()}, q)

        if expert_ids is not None:
            expert_ids[pre[:-1]] = block(h, own)[2]
        # one layer's activations at a time are kept for backward
        h, layer_kl = _kept_whole(lambda h, own, block=block:
                                  block(h, own)[:2])(h, own)
        kl = kl + layer_kl
    return q.act(_rms(h, params["norm.weight"], sizes["rms_norm_eps"])), kl


def reference_logits(sizes, params, x, q, expert_ids=None):
    """Float32 logits (B, T, V) of the network on token ids ``x``;
    ``expert_ids``, a dict, is filled with each layer's chosen expert
    ids under the layer's name (``layers.1``)."""
    h, _ = _hidden(sizes, params, x, q, expert_ids)
    return q.out(q.inp(h) @ q.inp(params["head.weight"]).T
                 ).astype(jnp.float32)


def reference_loss(sizes, params, x, y, q, key):
    """Per-sequence mean cross-entropy of the network, plus each
    sequence's ``L_I - stop_gradient(L_I)``: the loss read is the LM
    loss, its gradient the LM loss's and the indexer's (but under
    ``no_kl_gradient``). ``params`` hold every leaf in the dtype to
    compute in; ``q.inp`` is called on every operand of a matrix
    product, ``q.out`` on its result and ``q.act`` on every array kept
    between products (``correctness.Rounding``: nothing for the
    reference). The router's product, the index scores' sum and the
    indexer's head weights stay in float32 under every rounding, as the
    policy keeps them. The step's ``key`` goes unused: nothing here is
    drawn. The head and the loss go a block of ``REF_QUERY_BLOCK``
    positions at a time."""
    h, kl = _hidden(sizes, params, x, q)
    b, t, c = h.shape
    labels = y.astype(jnp.int32)
    block = _blocked(t)
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
    loss = -jnp.mean(logp.swapaxes(0, 1).reshape(b, t), axis=-1)
    if sizes.get("planted_fault") != "no_kl_gradient":
        loss = loss + (kl - jax.lax.stop_gradient(kl))
    return loss, {}


def block_witness(sizes, policy, weights, batches, ctx):
    """``{"sparse_block_gap": .., "selection_differ_share": ..}``: the
    program's attention block (``models.KeyeAttention`` on ``ctx``
    holding the seed's leaves of the first layer) against
    ``_attention_block`` in float32, on the layer's normed embedding of
    the first batch's tokens: the distance between the two outputs over
    the reference's norm; and the share of the reference's selected
    (query, key) pairs that the program's indexer (its own block, then
    ``ops.sparse_attention``'s scores and top-k) does not select. What
    the step's comparison of norms cannot see of the selection (the
    latest keys for the indexer's, scores without the ReLU) moves both."""
    from mxnet_tpu import autograd, models
    from mxnet_tpu.ndarray.ndarray import _wrap
    from mxnet_tpu.ops.sparse_attention import index_scores, select
    from benchmark import correctness

    pre = "layers.0."
    own = _own(weights, pre + "attn.")
    heads, kv, d, ih, idim, topk = _widths(sizes)
    block = models.KeyeAttention(sizes["hidden_size"], heads, kv, d,
                                 sizes["rope_theta"], (ih, idim), topk,
                                 sizes["rms_norm_eps"])
    block.initialize(ctx=ctx)
    _load(block, weights, pre + "attn.", policy)
    embed = jnp.asarray(weights["embed.weight"])
    x = _rms(embed[batches[0][0]].astype(jnp.float32),
             jnp.asarray(weights[pre + "attn_norm.weight"]),
             sizes["rms_norm_eps"]).astype(embed.dtype)
    with autograd.pause():
        got = block(_wrap(x))._data.astype(jnp.float32)
        qi, ki, w = (a._data for a in block.indexer(_wrap(x)))
    own32 = {k: jnp.asarray(v, jnp.float32) for k, v in own.items()}
    x32 = x.astype(jnp.float32)
    t = x.shape[1]
    bq = _blocked(t)

    def missed(own, x, qi, ki, w):
        ref_qi, ref_ki, ref_w = _indexer(sizes, x, _own(own, "indexer."),
                                         correctness.Rounding)

        def one(j):
            start = j * bq

            def rows(a):
                return jax.lax.dynamic_slice_in_dim(a, start, bq, axis=1)

            want = _selection(sizes, _scores(sizes, rows(ref_qi), ref_ki,
                                             rows(ref_w),
                                             correctness.Rounding), start)
            have = select(index_scores(rows(qi), ki, rows(w)), topk, start)
            return jnp.sum(want & ~have), jnp.sum(want)

        miss, total = jax.lax.map(one, jnp.arange(t // bq))
        return jnp.sum(miss) / jnp.sum(total)

    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(lambda x, own: _attention_block(
            sizes, x, own, correctness.Rounding))(x32, own32)
        share = jax.jit(missed)(own32, x32, qi, ki, w)
    return {"sparse_block_gap": float(jnp.linalg.norm(got - ref)
                                      / jnp.linalg.norm(ref)),
            "selection_differ_share": float(share)}


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
