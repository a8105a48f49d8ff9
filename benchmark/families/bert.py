"""Family ``bert``: the encoder language model that ``models.BERTModel``
builds, trained on a per-position cross-entropy.

Four things live here, found by the family's name: the weights (one
jitted call from the seed), the program side (the Gluon net holding
them, its loss, its batches), the plain reference in straightforward
``jax.numpy`` (it imports nothing of ``mxnet_tpu``), and the FLOPs a
step needs from the layers' shapes at two a multiply-add.

The reference follows THIS REPO's model, which is not Devlin et al.'s
BERT in these points (``mxnet_tpu/models/transformer.py``; the
configuration's file lists them under ``assumed``): pre-norm blocks and
a final LayerNorm; token and learned position embeddings only (no
segment embedding, no embedding LayerNorm); no padding mask; one fused
q/k/v projection; no pooler and no next-sentence head; an untied output
head; the loss is the mean cross-entropy over every position of a
sequence, one number a sequence. GELU is the exact (erf) form, LayerNorm
uses the biased variance and eps 1e-5, attention scales by
1/sqrt(head size). Dropout (``hidden_dropout_prob``) falls on each
layer's attention output and on its feed-forward output, before the
residual sum, and nowhere else; the masks come from the step's key as
the configuration's ``assumed.rng`` states. Parameter names are the
net's attribute paths (``layers.0.attn.qkv.weight``).
"""
import math

import jax
import jax.numpy as jnp

from benchmark.seeding import seed_key

LN_EPS = 1e-5
INIT_STD = 0.02  # BERT's initializer_range


# ---------------------------------------------------------------------------
# the layers, from the sizes alone
# ---------------------------------------------------------------------------

def param_shapes(sizes):
    """name -> (shape, kind) for every leaf."""
    c, f, v = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["vocab_size"]
    shapes = {"embed.weight": ((v, c), "normal"),
              "pos_embed.weight": ((sizes["max_position_embeddings"], c),
                                   "normal"),
              "ln_f.gamma": ((c,), "ones"), "ln_f.beta": ((c,), "zeros"),
              "head.weight": ((v, c), "normal"),
              "head.bias": ((v,), "zeros")}
    for i in range(sizes["num_hidden_layers"]):
        pre = f"layers.{i}"
        for name, cout, cin in (("attn.qkv", 3 * c, c), ("attn.proj", c, c),
                                ("ffn1", f, c), ("ffn2", c, f)):
            shapes[f"{pre}.{name}.weight"] = ((cout, cin), "normal")
            shapes[f"{pre}.{name}.bias"] = ((cout,), "zeros")
        for ln in ("ln1", "ln2"):
            shapes[f"{pre}.{ln}.gamma"] = ((c,), "ones")
            shapes[f"{pre}.{ln}.beta"] = ((c,), "zeros")
    return shapes


def is_state(name):
    """Leaves the step rewrites without a gradient: none here."""
    return False


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add, forward
    once and backward twice: per token the four projections and the two
    feed-forward products of each layer and the output head; per
    sequence the two attention products (scores, weighted values) of
    each layer. Bytes are the least HBM traffic of the three passes in
    the activations' four bytes: input, output and weight of a product
    once each pass; of attention only q, k, v and the result, since the
    algorithm need not write its T x T scores. The embedding look-ups,
    LayerNorm, softmax, GELU and the update are not matrix work and are
    not here; nothing is counted for recomputation."""
    c, f, v = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["vocab_size"]
    n, b, t = sizes["num_hidden_layers"], traffic["batch"], traffic["seq"]
    tok, nbytes = b * t, 4

    def product(name, cin, cout):
        return (name, float(3 * 2 * tok * cin * cout),
                float(3 * nbytes * (tok * (cin + cout) + cin * cout)))

    out = []
    for i in range(n):
        pre = f"layers.{i}"
        out.append(product(f"{pre}.attn.qkv", c, 3 * c))
        out.append((f"{pre}.attn.products",
                    float(3 * 2 * b * 2 * t * t * c),
                    float(3 * nbytes * 4 * tok * c)))
        out.append(product(f"{pre}.attn.proj", c, c))
        out.append(product(f"{pre}.ffn1", c, f))
        out.append(product(f"{pre}.ffn2", f, c))
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
    """All leaves in one jitted call, float32: embeddings and matrices
    normal with std 0.02, biases and beta zero, gamma one."""
    if policy != "f32":
        raise ValueError(f"bert: unknown dtype policy {policy!r}")
    shapes = param_shapes(sizes)

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            if kind == "normal":
                leaves[name] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif kind == "ones":
                leaves[name] = jnp.ones(shape, jnp.float32)
            else:
                leaves[name] = jnp.zeros(shape, jnp.float32)
        return leaves

    return jax.jit(build)(seed_key(seed, 0))


def make_batches(sizes, policy, traffic, seed):
    """``n_batches`` pairs of token ids and labels, uniform over the
    vocabulary, every row its own draw, in one jitted call."""
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

def build_program(sizes, policy, weights, ctx, sample_x):
    """``models.BERTModel`` on ``ctx`` holding ``weights``, and its
    loss. Shapes resolve by the program's own eager forward on one row,
    as a user's first call does (``operator_tune`` measures the
    attention candidates there)."""
    from mxnet_tpu import gluon, models
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = models.BERTModel(
        vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        hidden_size=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        dropout=sizes["hidden_dropout_prob"])
    net.initialize(ctx=ctx)
    net(_wrap(sample_x[:1])).wait_to_read()
    params = net._collect_params_with_prefix()
    if set(params) != set(weights):
        raise RuntimeError("the net's parameters and the benchmark's "
                           "differ: " + str(sorted(set(params)
                                                   ^ set(weights))[:6]))
    for name, p in params.items():
        # a copy: the fused step donates what the net holds
        p.set_data(_wrap(jnp.array(weights[name], copy=True)))
    # (B, T, V) logits against (B, T) labels: the mean over a sequence's
    # positions, one loss a sequence
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _ln(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * gamma + beta


def _dense(x, p, name, q):
    return q.out(q.inp(x) @ q.inp(p[f"{name}.weight"]).T) \
        + p[f"{name}.bias"]


def _dropout(h, rate, key, site):
    """Inverted dropout under the mask of the step's ``site``-th
    drawing operator: a Bernoulli draw of ``h``'s shape under
    ``fold_in(key, site)``. The program numbers from 1, in the order of
    the forward pass, every operator that may draw: in each layer the
    attention output's dropout, the feed-forward activation (which draws
    nothing for GELU but takes a number) and the feed-forward output's
    dropout."""
    if not rate:
        return h
    keep = 1.0 - rate
    mask = jax.random.bernoulli(jax.random.fold_in(key, site), keep,
                                h.shape)
    return h * mask.astype(h.dtype) / keep


def reference_loss(sizes, params, x, y, q, key):
    """Per-sequence mean cross-entropy of the network, and no state.
    ``params`` hold every leaf in the dtype to compute in; ``q.inp`` is
    called on every operand of a matrix product and ``q.out`` on its
    result (``correctness.Rounding``: nothing for the reference);
    ``key`` is the step's random key."""
    heads = sizes["num_attention_heads"]
    b, t = x.shape
    c = sizes["hidden_size"]
    d = c // heads
    rate = sizes["hidden_dropout_prob"]

    def layer(h, p, key, i):
        a = _dense(_ln(h, p["ln1.gamma"], p["ln1.beta"]), p, "attn.qkv", q)
        a = a.reshape(b, t, 3, heads, d).transpose(2, 0, 3, 1, 4)
        scores = q.out(jnp.einsum("bhqd,bhkd->bhqk", q.inp(a[0]),
                                  q.inp(a[1]))) / math.sqrt(d)
        probs = jax.nn.softmax(scores, axis=-1)
        o = q.out(jnp.einsum("bhqk,bhkd->bhqd", q.inp(probs),
                             q.inp(a[2])))
        o = o.transpose(0, 2, 1, 3).reshape(b, t, c)
        h = h + _dropout(_dense(o, p, "attn.proj", q), rate, key,
                         3 * i + 1)
        f = _dense(_ln(h, p["ln2.gamma"], p["ln2.beta"]), p, "ffn1", q)
        f = jax.nn.gelu(f, approximate=False)
        return h + _dropout(_dense(f, p, "ffn2", q), rate, key, 3 * i + 3)

    h = params["embed.weight"][x] + params["pos_embed.weight"][:t][None]
    for i in range(sizes["num_hidden_layers"]):
        pre = f"layers.{i}."
        own = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        # one layer's activations at a time are kept for backward
        h = jax.checkpoint(layer, static_argnums=3)(h, own, key, i)
    h = _ln(h, params["ln_f.gamma"], params["ln_f.beta"])

    def head_loss(h, w, bias, labels):
        logits = (q.out(q.inp(h) @ q.inp(w).T) + bias).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked[..., 0], axis=-1)

    loss = jax.checkpoint(head_loss)(
        h, params["head.weight"], params["head.bias"],
        y.astype(jnp.int32))
    return loss, {}
