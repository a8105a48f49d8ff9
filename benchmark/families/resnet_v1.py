"""Family ``resnet_v1``: ResNet v1 with bottleneck blocks (He et al.,
arXiv:1512.03385, Table 1) as ``gluon.model_zoo.vision.ResNetV1`` over
``BottleneckV1`` builds it.

Four things live here, found by the family's name:

- the weights, made on the device from the seed in one jitted call;
- the program side: the Gluon net with those weights, its loss, its
  batches;
- the plain reference: the same network, training-mode BatchNorm, loss
  and per-sample gradient seed in straightforward ``jax.numpy``. It
  imports nothing of ``mxnet_tpu``;
- the FLOPs the model needs per step, from the layers' shapes, at two a
  multiply-add.

What the reference follows that He et al. do not state (the repo's model
is the Gluon zoo's v1): the stride of a down-sampling block sits on its
first 1x1 convolution; the 1x1 convolutions of a block carry a bias, the
3x3, the 7x7 stem and the shortcut's do not; BatchNorm uses the batch's
biased variance, eps 1e-5, and keeps running statistics with momentum
0.9. Parameter names are the net's attribute paths
(``features.4.0.body.0.weight``), which is also how the reference names
its own leaves.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.seeding import seed_key

BN_EPS = 1e-5
BN_MOMENTUM = 0.9
BN_LEAVES = ("gamma", "beta", "running_mean", "running_var")


# ---------------------------------------------------------------------------
# the layers, from the sizes alone
# ---------------------------------------------------------------------------

def layer_table(sizes):
    """Every parameterised layer in forward order, as plain tuples:
    ``("conv", name, cin, cout, k, stride, pad, bias, in_hw, out_hw)``,
    ``("bn", name, channels, hw)`` and ``("dense", name, cin, cout)``.
    The reference, the weights and the FLOPs count all read this."""
    layers, channels = sizes["layers"], sizes["channels"]
    hw = sizes["image"]
    out = []

    def conv(name, cin, cout, k, stride, pad, bias, hw):
        ohw = (hw + 2 * pad - k) // stride + 1
        out.append(("conv", name, cin, cout, k, stride, pad, bias, hw, ohw))
        return ohw

    if sizes.get("thumbnail"):
        hw = conv("features.0", 3, channels[0], 3, 1, 1, False, hw)
        first_stage = 1
    else:
        hw = conv("features.0", 3, channels[0], 7, 2, 3, False, hw)
        out.append(("bn", "features.1", channels[0], hw))
        hw = (hw + 2 - 3) // 2 + 1  # MaxPool2D(3, 2, 1)
        first_stage = 4
    cin = channels[0]
    for s, (n_blocks, cout) in enumerate(zip(layers, channels[1:])):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            pre = f"features.{first_stage + s}.{b}"
            mid = cout // 4
            h1 = conv(f"{pre}.body.0", cin, mid, 1, stride, 0, True, hw)
            out.append(("bn", f"{pre}.body.1", mid, h1))
            conv(f"{pre}.body.3", mid, mid, 3, 1, 1, False, h1)
            out.append(("bn", f"{pre}.body.4", mid, h1))
            conv(f"{pre}.body.6", mid, cout, 1, 1, 0, True, h1)
            out.append(("bn", f"{pre}.body.7", cout, h1))
            if b == 0 and cout != cin:
                conv(f"{pre}.downsample.0", cin, cout, 1, stride, 0, False,
                     hw)
                out.append(("bn", f"{pre}.downsample.1", cout, h1))
            hw, cin = h1, cout
    out.append(("dense", "output", cin, sizes["classes"]))
    return out


def param_shapes(sizes):
    """name -> (shape, kind) for every leaf, running statistics too."""
    shapes = {}
    for layer in layer_table(sizes):
        kind, name = layer[0], layer[1]
        if kind == "conv":
            _, _, cin, cout, k, _, _, bias, _, _ = layer
            shapes[f"{name}.weight"] = ((cout, cin, k, k), "conv_w")
            if bias:
                shapes[f"{name}.bias"] = ((cout,), "zeros")
        elif kind == "bn":
            c = layer[2]
            shapes[f"{name}.gamma"] = ((c,), "ones")
            shapes[f"{name}.beta"] = ((c,), "zeros")
            shapes[f"{name}.running_mean"] = ((c,), "zeros")
            shapes[f"{name}.running_var"] = ((c,), "ones")
        else:
            _, _, cin, cout = layer
            shapes[f"{name}.weight"] = ((cout, cin), "dense_w")
            shapes[f"{name}.bias"] = ((cout,), "zeros")
    return shapes


def is_state(name):
    """Leaves the step rewrites without a gradient (BatchNorm's running
    statistics)."""
    return name.rsplit(".", 1)[-1] in ("running_mean", "running_var")


def param_dtype(name, policy):
    """``bf16_bn_f32``: everything bf16 but BatchNorm's four leaves."""
    if policy == "bf16_bn_f32" and name.rsplit(".", 1)[-1] not in BN_LEAVES:
        return jnp.bfloat16
    return jnp.float32


def matrix_layers(sizes, traffic):
    """The matrix work one training step needs, a layer at a time:
    ``[(name, flops, bytes), ...]``. FLOPs at two a multiply-add, forward
    once and backward twice (input and weight gradients); the first
    layer's input is the image, whose gradient training does not need,
    so it has two passes. Bytes are the least HBM traffic of those
    passes: input, output and weight of the layer once each pass, in the
    activations' two bytes. BatchNorm, ReLU, pooling and the update are
    not matrix work and are not here; nothing is counted for
    recomputation."""
    b, nbytes = traffic["batch"], 2
    out = []
    for layer in layer_table(sizes):
        if layer[0] == "conv":
            _, name, cin, cout, k, _, _, _, ihw, ohw = layer
            macs = b * ohw * ohw * cout * cin * k * k
            elems = b * (cin * ihw * ihw + cout * ohw * ohw) \
                + cout * cin * k * k
        elif layer[0] == "dense":
            _, name, cin, cout = layer
            macs = b * cin * cout
            elems = b * (cin + cout) + cin * cout
        else:
            continue
        passes = 3 if out else 2
        out.append((name, float(passes * 2 * macs),
                    float(passes * nbytes * elems)))
    return out


def needed_flops(sizes, traffic):
    """FLOPs one training step needs: the sum over ``matrix_layers``."""
    return sum(f for _, f, _ in matrix_layers(sizes, traffic))


def work_units(sizes, traffic):
    """What one step processes, under the name users quote."""
    return {"images": traffic["batch"]}


# ---------------------------------------------------------------------------
# weights and batches, on the device from the seed
# ---------------------------------------------------------------------------

def make_weights(sizes, policy, seed):
    """All leaves in one jitted call, in the type they are trained in.
    Convolutions and the classifier: normal, std sqrt(2 / fan_in) (He et
    al., arXiv:1502.01852); biases and beta zero; gamma one; running
    mean zero and variance one."""
    shapes = param_shapes(sizes)

    def build(key):
        leaves = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            dt = param_dtype(name, policy)
            if kind in ("conv_w", "dense_w"):
                fan_in = math.prod(shape[1:])
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                leaves[name] = (w * math.sqrt(2.0 / fan_in)).astype(dt)
            elif kind == "ones":
                leaves[name] = jnp.ones(shape, dt)
            else:
                leaves[name] = jnp.zeros(shape, dt)
        return leaves

    return jax.jit(build)(seed_key(seed, 0))


def make_batches(sizes, policy, traffic, seed):
    """``n_batches`` pairs (images uniform in [-1, 1], labels uniform
    over the classes), every row its own draw, in one jitted call."""
    n, b, s = traffic["n_batches"], traffic["batch"], sizes["image"]
    xdt = jnp.bfloat16 if policy == "bf16_bn_f32" else jnp.float32

    def build(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (n, b, 3, s, s), jnp.float32, -1.0, 1.0)
        y = jax.random.randint(ky, (n, b), 0, sizes["classes"])
        return x.astype(xdt), y.astype(jnp.float32)

    xs, ys = jax.jit(build)(seed_key(seed, 1))
    return [(xs[i], ys[i]) for i in range(n)]


# ---------------------------------------------------------------------------
# the program side (the system under test)
# ---------------------------------------------------------------------------

def build_program(sizes, policy, weights, ctx, sample_x):
    """The Gluon net on ``ctx`` holding ``weights``, and its loss. The
    shapes resolve by the program's own eager forward on one row, as a
    user's first call does (that is also where ``operator_tune``
    measures)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ndarray.ndarray import _wrap

    net = vision.ResNetV1(vision.BottleneckV1, list(sizes["layers"]),
                          list(sizes["channels"]),
                          classes=sizes["classes"],
                          thumbnail=bool(sizes.get("thumbnail")))
    net.initialize(ctx=ctx)
    net(_wrap(sample_x[:1].astype(jnp.float32))).wait_to_read()
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
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _conv(x, w, stride, pad, q):
    return q.out(jax.lax.conv_general_dilated(
        q.inp(x), q.inp(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))


def _bn(x, p, name, new_stats):
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.var(x, axis=(0, 2, 3))
    f32 = jnp.float32
    new_stats[f"{name}.running_mean"] = jax.lax.stop_gradient(
        p[f"{name}.running_mean"].astype(f32) * BN_MOMENTUM
        + mean.astype(f32) * (1 - BN_MOMENTUM))
    new_stats[f"{name}.running_var"] = jax.lax.stop_gradient(
        p[f"{name}.running_var"].astype(f32) * BN_MOMENTUM
        + var.astype(f32) * (1 - BN_MOMENTUM))
    r = lambda v: v.reshape(1, -1, 1, 1)
    xn = (x - r(mean)) * jax.lax.rsqrt(r(var) + BN_EPS)
    return xn * r(p[f"{name}.gamma"].astype(x.dtype)) \
        + r(p[f"{name}.beta"].astype(x.dtype))


def _maxpool_3_2_1(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])


def reference_loss(sizes, params, x, y, q, key):
    """Per-sample softmax cross-entropy of the network in training mode,
    and the new running statistics. ``params`` hold every leaf in the
    dtype to compute in; ``q.inp`` is called on every operand of a
    convolution or matrix product, ``q.out`` on its result and ``q.act``
    on every activation kept between layers (``correctness.Rounding``:
    nothing for the reference, a lower precision's rounding for its
    control). The step's random ``key`` goes unused: nothing here is
    drawn."""
    dt = params["features.0.weight"].dtype
    new_stats = {}
    conv_of = {l[1]: l for l in layer_table(sizes) if l[0] == "conv"}

    def conv(p, name, h):
        _, _, _, _, _, stride, pad, bias, _, _ = conv_of[name]
        h = _conv(h, p[f"{name}.weight"], stride, pad, q)
        if bias:
            h = h + p[f"{name}.bias"].reshape(1, -1, 1, 1)
        return h

    h = conv(params, "features.0", x.astype(dt))
    if sizes.get("thumbnail"):
        first_stage = 1
    else:
        h = _maxpool_3_2_1(q.act(jax.nn.relu(
            _bn(h, params, "features.1", new_stats))))
        first_stage = 4

    def block(pre, h, p):
        st = {}
        r = h
        h = conv(p, f"{pre}.body.0", h)
        h = q.act(jax.nn.relu(_bn(h, p, f"{pre}.body.1", st)))
        h = conv(p, f"{pre}.body.3", h)
        h = q.act(jax.nn.relu(_bn(h, p, f"{pre}.body.4", st)))
        h = q.act(_bn(conv(p, f"{pre}.body.6", h), p, f"{pre}.body.7", st))
        if f"{pre}.downsample.0" in conv_of:
            r = q.act(_bn(conv(p, f"{pre}.downsample.0", r), p,
                          f"{pre}.downsample.1", st))
        return q.act(jax.nn.relu(h + r)), st

    for s, n_blocks in enumerate(sizes["layers"]):
        for b in range(n_blocks):
            pre = f"features.{first_stage + s}.{b}"
            own = {k: v for k, v in params.items()
                   if k.startswith(pre + ".")}
            # one block's activations at a time are kept for backward
            h, st = jax.checkpoint(block, static_argnums=0)(pre, h, own)
            new_stats.update(st)
    h = jnp.mean(h, axis=(2, 3))
    logits = q.out(q.inp(h) @ q.inp(params["output.weight"]).T) \
        + params["output.bias"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    labels = y.astype(jnp.int32)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return loss, new_stats
