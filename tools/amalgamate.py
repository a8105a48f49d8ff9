#!/usr/bin/env python
"""Single-file model deployment (the amalgamation story).

ref: amalgamation/ (amalgamation.py + mxnet_predict0.cc) — the
reference squashes the predict API into ONE .cc so a trained model can
run on platforms where building the framework is impractical (mobile
JNI, emscripten). The TPU-native reinterpretation: the heavy runtime is
XLA and cannot (and should not) be amalgamated, but the DEPLOY artifact
can — this tool compiles a trained checkpoint (symbol JSON + params in
the reference binary format) into ONE self-contained Python file whose
only dependency is numpy. The generated file embeds the graph, the
weights (zlib+base64 npz), and a small numpy interpreter for the
inference op subset; it never imports jax or mxnet_tpu, so it runs
anywhere numpy does (CPython anywhere, pyodide, etc.).

Usage:
    python tools/amalgamate.py MODEL_PREFIX EPOCH -o predictor.py
    python predictor.py input.npy          # or import and predict(x)
"""
import argparse
import base64
import io
import json
import os
import sys
import zlib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# host-side packaging tool: force the CPU backend BEFORE any framework
# import — it must never take the chip from the job that holds it
import jax  # noqa: E402
jax.config.update("jax_platforms", "cpu")

_RUNTIME = '''
import ast
import base64
import io
import json
import sys
import zlib

import numpy as np


def _attrs(node):
    out = {}
    for k, v in node.get("attrs", {}).items():
        try:
            out[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            out[k] = v
    return out


def _pair(v, k=2):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    return (int(v),) * k


def _im2col(x, kh, kw, sh, sw, ph, pw, dh, dw):
    B, C, H, W = x.shape
    x = np.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    eh, ew = dh * (kh - 1) + 1, dw * (kw - 1) + 1
    Ho = (H + 2 * ph - eh) // sh + 1
    Wo = (W + 2 * pw - ew) // sw + 1
    cols = np.empty((B, C, kh, kw, Ho, Wo), x.dtype)
    for i in range(kh):
        for j in range(kw):
            cols[:, :, i, j] = x[:, :, i * dh:i * dh + Ho * sh:sh,
                                 j * dw:j * dw + Wo * sw:sw]
    return cols.reshape(B, C * kh * kw, Ho * Wo), Ho, Wo


def _conv(x, w, b, a):
    kh, kw = _pair(a["kernel"])
    sh, sw = _pair(a.get("stride", 1))
    ph, pw = _pair(a.get("pad", 0))
    dh, dw = _pair(a.get("dilate", 1))
    g = int(a.get("num_group", 1))
    B, C = x.shape[:2]
    F = w.shape[0]
    outs = []
    for gi in range(g):
        xg = x[:, gi * (C // g):(gi + 1) * (C // g)]
        wg = w[gi * (F // g):(gi + 1) * (F // g)]
        cols, Ho, Wo = _im2col(xg, kh, kw, sh, sw, ph, pw, dh, dw)
        wm = wg.reshape(F // g, -1)
        outs.append(np.einsum("fk,bkp->bfp", wm, cols)
                    .reshape(B, F // g, Ho, Wo))
    out = np.concatenate(outs, axis=1)
    if b is not None:
        out = out + b.reshape(1, -1, 1, 1)
    return out


def _windows(x, kh, kw, sh, sw):
    B, C, H, W = x.shape
    Ho, Wo = (H - kh) // sh + 1, (W - kw) // sw + 1
    win = np.empty((B, C, Ho, Wo, kh * kw), x.dtype)
    for i in range(kh):
        for j in range(kw):
            win[..., i * kw + j] = x[:, :, i:i + Ho * sh:sh,
                                     j:j + Wo * sw:sw]
    return win


def _pool(x, a):
    kind = a.get("pool_type", "max")
    if a.get("global_pool", False):
        r = x.max(axis=(2, 3), keepdims=True) if kind == "max" \\
            else x.mean(axis=(2, 3), keepdims=True)
        return r
    kh, kw = _pair(a["kernel"])
    # framework default stride is 1, NOT the kernel size (ops/nn.py)
    sh, sw = _pair(a.get("stride", 1))
    ph, pw = _pair(a.get("pad", 0))
    pad_val = -np.inf if kind == "max" else 0.0
    x = np.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
               constant_values=pad_val)
    win = _windows(x, kh, kw, sh, sw)
    if kind == "max":
        return win.max(-1)
    if kind == "avg":
        if a.get("count_include_pad", True):
            return win.sum(-1) / (kh * kw)
        ones = np.pad(np.ones(
            (x.shape[0], x.shape[1], x.shape[2] - 2 * ph,
             x.shape[3] - 2 * pw), x.dtype),
            ((0, 0), (0, 0), (ph, ph), (pw, pw)))
        counts = _windows(ones, kh, kw, sh, sw).sum(-1)
        return win.sum(-1) / np.maximum(counts, 1.0)
    raise NotImplementedError("pool_type " + kind)


def _softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def _reshape_spec(cur, spec):
    # MXNet special codes (matrix_op-inl.h): 0 copy, -1 infer,
    # -2 copy rest, -3 merge two; -4 (split) is refused loudly
    out, i, j = [], 0, 0
    spec = list(spec)
    while j < len(spec):
        s = spec[j]
        if s == 0:
            out.append(cur[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(cur[i:]); i = len(cur)
        elif s == -3:
            out.append(cur[i] * cur[i + 1]); i += 2
        elif s == -4:
            raise NotImplementedError(
                "reshape code -4 not supported in amalgamated runtime")
        else:
            out.append(int(s)); i += 1
        j += 1
    return tuple(out)


def _act(x, t):
    if t == "relu":
        return np.maximum(x, 0)
    if t == "sigmoid":
        return 1.0 / (1.0 + np.exp(-x))
    if t == "tanh":
        return np.tanh(x)
    if t == "softrelu":
        return np.log1p(np.exp(-np.abs(x))) + np.maximum(x, 0)
    raise NotImplementedError("act_type " + t)


def _forward(graph, params, data):
    vals = {}
    unbound = []
    nodes = graph["nodes"]

    def inp(node, i):
        ni, oi = node["inputs"][i][0], node["inputs"][i][1]
        return vals[ni][oi]

    def inps(node):
        return [vals[e[0]][e[1]] for e in node["inputs"]]

    for idx, node in enumerate(nodes):
        op, a = node["op"], _attrs(node)
        if op == "null":
            # exactly ONE variable may be unbound: the data input
            # (mxnet_predict0's MXPredSetInput("data", ...) convention).
            # A second unbound name means a missing/renamed weight, and
            # binding the user's input there would return plausible
            # garbage — fail loudly instead.
            if node["name"] in params:
                v = params[node["name"]]
            elif unbound and unbound != [node["name"]]:
                raise KeyError(
                    "unbound variables %r and %r: the embedded params "
                    "are missing a weight" % (unbound[0], node["name"]))
            else:
                unbound.append(node["name"])
                v = data
            vals[idx] = [np.asarray(v)]
            continue
        x = inps(node)
        if op == "Convolution":
            bias = None if a.get("no_bias", False) else x[2]
            out = _conv(x[0], x[1], bias, a)
        elif op == "FullyConnected":
            h = x[0].reshape(x[0].shape[0], -1) \\
                if a.get("flatten", True) else x[0]
            out = h @ x[1].T
            if not a.get("no_bias", False):
                out = out + x[2]
        elif op == "Activation":
            out = _act(x[0], a["act_type"])
        elif op == "LeakyReLU":
            t = a.get("act_type", "leaky")
            s = float(a.get("slope", 0.25))
            if t == "leaky":
                out = np.where(x[0] > 0, x[0], s * x[0])
            elif t == "elu":
                out = np.where(x[0] > 0, x[0],
                               s * (np.exp(x[0]) - 1.0))
            else:
                raise NotImplementedError("LeakyReLU act_type " + t)
        elif op == "BatchNorm":
            g, b, mean, var = x[1], x[2], x[3], x[4]
            eps = float(a.get("eps", 1e-3))
            if a.get("fix_gamma", True):
                g = np.ones_like(g)
            shape = (1, -1) + (1,) * (x[0].ndim - 2)
            out = ((x[0] - mean.reshape(shape))
                   / np.sqrt(var.reshape(shape) + eps)
                   * g.reshape(shape) + b.reshape(shape))
        elif op == "Pooling":
            out = _pool(x[0], a)
        elif op in ("Flatten", "flatten"):
            out = x[0].reshape(x[0].shape[0], -1)
        elif op in ("Reshape", "reshape"):
            out = x[0].reshape(_reshape_spec(x[0].shape, a["shape"]))
        elif op == "softmax":
            out = _softmax(x[0], int(a.get("axis", -1)))
        elif op == "log_softmax":
            out = np.log(_softmax(x[0], int(a.get("axis", -1))))
        elif op == "SoftmaxOutput":
            # inference: ignore the label; match the framework's
            # normalization domain (axis 1 for multi_output, else the
            # whole flattened sample)
            if a.get("multi_output", False):
                out = _softmax(x[0], 1)
            else:
                out = _softmax(x[0].reshape(x[0].shape[0], -1),
                               -1).reshape(x[0].shape)
        elif op == "Dropout":
            out = x[0]                  # inference: identity
        elif op == "clip":
            out = np.clip(x[0], float(a["a_min"]), float(a["a_max"]))
        elif op in ("elemwise_add", "_plus", "_Plus", "broadcast_add"):
            out = x[0] + x[1]
        elif op in ("elemwise_mul", "broadcast_mul"):
            out = x[0] * x[1]
        elif op == "Concat":
            out = np.concatenate(x, axis=int(a.get("dim", 1)))
        elif op == "Embedding":
            out = x[1][x[0].astype(np.int64)]
        else:
            raise NotImplementedError(
                "amalgamated runtime does not implement op " + op)
        vals[idx] = [out]
    return [vals[e[0]][e[1]] for e in graph["heads"]]


_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        raw = zlib.decompress(base64.b64decode(PARAMS_B64))
        with np.load(io.BytesIO(raw)) as z:
            _PARAMS = {k: z[k] for k in z.files}
    return _PARAMS


def predict(data):
    """data: numpy array shaped like the training 'data' input."""
    outs = _forward(GRAPH, _params(), np.asarray(data, np.float32))
    return outs[0] if len(outs) == 1 else outs


if __name__ == "__main__":
    if len(sys.argv) > 1:
        x = np.load(sys.argv[1])
    else:
        x = np.random.RandomState(0).rand(*INPUT_SHAPE).astype("float32")
    y = predict(x)
    np.save(sys.argv[2] if len(sys.argv) > 2 else "prediction.npy", y)
    print("output shape", y.shape)
    print(y.ravel()[:8])
'''


def amalgamate(prefix, epoch, out_path, input_shape=(1, 3, 224, 224)):
    """Read a checkpoint with the full framework, emit the standalone
    predictor file."""
    from mxnet_tpu import model as mx_model
    symbol, arg_params, aux_params = mx_model.load_checkpoint(prefix,
                                                              epoch)
    graph = json.loads(symbol.tojson())
    params = {}
    for name, v in {**arg_params, **aux_params}.items():
        params[name] = v.asnumpy()
    buf = io.BytesIO()
    import numpy as onp
    onp.savez(buf, **params)
    blob = base64.b64encode(zlib.compress(buf.getvalue(), 9)).decode()

    header = (
        '#!/usr/bin/env python\n'
        '"""Self-contained predictor (generated by mxnet_tpu '
        'tools/amalgamate.py).\n\n'
        f'Source checkpoint: {os.path.basename(prefix)}-{epoch:04d}. '
        'Only dependency: numpy.\n"""\n')
    body = (f"GRAPH = {json.dumps(graph)}\n\n"
            f"INPUT_SHAPE = {tuple(input_shape)}\n\n"
            f'PARAMS_B64 = "{blob}"\n')
    with open(out_path, "w") as f:
        f.write(header + body + _RUNTIME)
    os.chmod(out_path, 0o755)
    return out_path


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("prefix", help="checkpoint prefix "
                                  "(PREFIX-symbol.json + PREFIX-NNNN.params)")
    p.add_argument("epoch", type=int)
    p.add_argument("-o", "--out", default="predictor.py")
    p.add_argument("--input-shape", default="1,3,224,224",
                   help="comma shape embedded for the CLI demo")
    args = p.parse_args(argv)
    shape = tuple(int(s) for s in args.input_shape.split(","))
    path = amalgamate(args.prefix, args.epoch, args.out, shape)
    size_kb = os.path.getsize(path) / 1024
    print(f"wrote {path} ({size_kb:.1f} KiB, numpy-only)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
