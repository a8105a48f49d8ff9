"""Combined-mesh pipelined transformer LM: dp x tp x sp x ep x pipe in
ONE jax.sharding.Mesh.

VERDICT r3 item 6 asked for the pipeline axis folded into the SAME mesh
as data/tensor/sequence/expert parallelism (it was previously exercised
on its own 'pipe' mesh), plus structural verification that the compiled
HLO contains the expected collectives. This module is that composition,
kept pure-jax (no gluon dependency) so the whole training step is one
inspectable XLA program:

- 'pipe'  : GPipe microbatch schedule, expressed as a lax.scan over
            ticks with lax.ppermute activation shifts. The pipe axis is
            the ONLY manual axis (jax.shard_map(axis_names={'pipe'})) —
            everything inside a stage stays GSPMD, so the same layer
            code composes with the other four axes.
- 'data'  : batch sharded; XLA inserts the gradient all-reduce.
- 'model' : Megatron-style tensor parallel (attention heads + MoE
            experts sharded) — expert parallel rides the same axis, as
            in the rest of this framework (parallel/moe.py).
- 'seq'   : two selectable formulations (attention= kwarg):
            "gspmd" (default) — activations sequence-sharded,
            Megatron-SP style, XLA all-gathers K/V for the causal
            product; "ring" — TRUE ring attention
            (parallel/ring_attention.py) as a NESTED partial-manual
            shard_map over 'seq' inside the 'pipe'-manual stage: K/V
            (and their global positions) rotate around the ICI ring
            with online softmax, O(T_local^2) memory.

The reference has no pipeline parallelism at all (SURVEY.md §2.4;
closest is staged PartialForward, graph_executor.cc:82) — this is part
of the beyond-reference distributed surface, designed TPU-first.

The GPipe loop here differs from pipeline.py's inference-only
pipeline_apply: lax.scan (reverse-differentiable) instead of
lax.fori_loop, so the FULL training step (forward, backward through the
ppermute schedule, Adam update) compiles as one XLA executable.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .train import adam_init, adam_apply

__all__ = ["init_pipeline_lm", "truncate_pipeline_lm",
           "pipeline_lm_shardings", "stage_params", "unstage_params",
           "build_pipeline_lm_step", "dense_lm_loss", "dense_lm_logits",
           "pipeline_lm_loss", "combined_mesh_drill"]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_pipeline_lm(seed: int, *, vocab: int, d_model: int,
                     n_layers: int, n_heads: int, d_head: int,
                     d_ff: int, n_experts: int) -> Dict:
    """Homogeneous pre-LN decoder stack with MoE FFNs; per-layer params
    stacked along a leading layer dimension so the stack is scan- and
    pipeline-friendly (stage s owns layers[s*per : (s+1)*per])."""
    rs = onp.random.RandomState(seed)

    def w(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / onp.sqrt(shape[-2])
        return jnp.asarray(rs.randn(*shape).astype("float32") * scale)

    L, D, H, K, F, E = n_layers, d_model, n_heads, d_head, d_ff, n_experts
    return {
        "embed": w(vocab, D, scale=0.02),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
            # fan-in is D: the default would read it off shape[-2] = H,
            # giving q.k logits a std of ~D/H (64 at 768/12) — attention
            # so close to one-hot that a bf16 rounding decorrelates the
            # logits, and no parity check on the chip means anything
            "wqkv": w(L, 3, D, H, K, scale=1.0 / onp.sqrt(D)),
            "wo": w(L, H, K, D, scale=1.0 / onp.sqrt(H * K)),
            "gate": w(L, D, E),
            "w1": w(L, E, D, F),
            "b1": jnp.zeros((L, E, F), jnp.float32),
            "w2": w(L, E, F, D, scale=1.0 / onp.sqrt(F)),
            "b2": jnp.zeros((L, E, D), jnp.float32),
        },
        "ln_f": jnp.ones((D,), jnp.float32),
        "head": w(D, vocab),
    }


def truncate_pipeline_lm(params: Dict, n_layers: int) -> Dict:
    """Layer-truncated draft model: the first ``n_layers`` of a stack
    with the embedding/head/final-norm shared — the standard
    self-drafting baseline for speculative decoding
    (serve2.DecodeEngine ``draft_params=``). Shares the leaves (no
    copy): vocab and d_model match the target by construction, which
    is exactly what the verify step requires."""
    L = params["layers"]["wqkv"].shape[0]
    n = int(n_layers)
    if not 1 <= n <= L:
        raise ValueError(
            f"truncate_pipeline_lm: n_layers must be in [1, {L}], "
            f"got {n}")
    out = dict(params)
    out["layers"] = {k: v[:n] for k, v in params["layers"].items()}
    return out


def pipeline_lm_shardings(mesh: Mesh, n_stage: int) -> Dict:
    """NamedSharding tree for the STAGED param layout (layer leaves
    reshaped to (n_stage, per_stage, ...)): stage dim on 'pipe',
    attention heads and MoE experts on 'model' (tp + ep)."""
    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    return {
        "embed": ns(),
        "layers": {
            "ln1": ns("pipe"), "ln2": ns("pipe"),
            "wqkv": ns("pipe", None, None, None, "model"),
            "wo": ns("pipe", None, "model"),
            "gate": ns("pipe", None, None, "model"),
            "w1": ns("pipe", None, "model"),
            "b1": ns("pipe", None, "model"),
            "w2": ns("pipe", None, "model"),
            "b2": ns("pipe", None, "model"),
        },
        "ln_f": ns(),
        "head": ns(),
    }


def stage_params(params: Dict, n_stage: int) -> Dict:
    """Reshape the (L, ...) layer leaves to (n_stage, L//n_stage, ...)."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda v: v.reshape((n_stage, v.shape[0] // n_stage) + v.shape[1:]),
        params["layers"])
    return out


def unstage_params(params_staged: Dict) -> Dict:
    """Inverse of :func:`stage_params`: collapse the leading
    (n_stage, per_stage) dims back to (L, ...) — the dense layout
    checkpoints store, so saved params stay stage-count-independent
    (mxnet_tpu/pipe restores them into any stage count dividing L)."""
    out = dict(params_staged)
    out["layers"] = jax.tree.map(
        lambda v: v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:]),
        params_staged["layers"])
    return out


# ---------------------------------------------------------------------------
# layer / forward
# ---------------------------------------------------------------------------

def _rmsnorm(h, scale):
    return h * scale * jax.lax.rsqrt(
        jnp.mean(jnp.square(h), axis=-1, keepdims=True) + 1e-6)


def _layer(lp, h, shard, attention="gspmd"):
    """One pre-LN block: causal MHA + top-1-gated MoE FFN.

    `shard(x, axes)` annotates GSPMD shardings (identity in the dense
    reference): activations (data, seq)-sharded, heads/experts on
    'model'.

    attention="gspmd": K/V are annotated seq-REPLICATED so XLA inserts
    the all-gather over 'seq' that makes the causal product
    q_local @ k_full legal — the Megatron-SP formulation.
    attention="ring": TRUE ring attention (parallel/ring_attention.py)
    as a nested partial-manual shard_map over 'seq' inside the
    'pipe'-manual stage — K/V rotate around the ICI ring with online
    softmax, O(T_local^2) memory, the long-context kernel composed into
    the five-axis mesh."""
    if attention not in ("gspmd", "ring"):
        raise ValueError(f"attention must be 'gspmd' or 'ring', "
                         f"got {attention!r}")
    B, T, D = h.shape
    H, K = lp["wo"].shape[0], lp["wo"].shape[1]

    hn = _rmsnorm(h, lp["ln1"])
    qkv = jnp.einsum("btd,cdhk->cbthk", hn, lp["wqkv"])
    if attention == "ring":
        from .ring_attention import ring_attention
        q = shard(qkv[0], ("data", "seq", "model", None))
        k = shard(qkv[1], ("data", "seq", "model", None))
        v = shard(qkv[2], ("data", "seq", "model", None))
        ctx = ring_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), mesh=None, seq_axis="seq",
            causal=True, scale=1.0 / onp.sqrt(K), nested=True,
        ).transpose(0, 2, 1, 3)
    else:
        q = shard(qkv[0], ("data", "seq", "model", None))
        k = shard(qkv[1], ("data", None, "model", None))
        v = shard(qkv[2], ("data", None, "model", None))
        logits = jnp.einsum("bthk,bshk->bhts", q, k) / onp.sqrt(K)
        causal = jnp.tril(jnp.ones((T, T), bool))
        att = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        ctx = jnp.einsum("bhts,bshk->bthk", att, v)
    h = h + shard(jnp.einsum("bthk,hkd->btd", ctx, lp["wo"]),
                  ("data", "seq", None))

    hn = _rmsnorm(h, lp["ln2"])
    E = lp["gate"].shape[-1]
    wts = jax.nn.softmax(jnp.einsum("btd,de->bte", hn, lp["gate"]))
    top1 = jax.nn.one_hot(jnp.argmax(wts, -1), E) * wts
    top1 = top1 / (jnp.sum(top1, -1, keepdims=True) + 1e-9)
    y = jnp.einsum("btd,edf->betf", hn, lp["w1"]) + lp["b1"][:, None, :]
    y = shard(jax.nn.gelu(y), ("data", "model", "seq", None))
    y = jnp.einsum("betf,efd->betd", y, lp["w2"]) + lp["b2"][:, None, :]
    h = h + shard(jnp.einsum("bte,betd->btd", top1, y),
                  ("data", "seq", None))
    return h


def _mesh_shard(mesh):
    def shard(x, axes):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*axes)))
    return shard


def _no_shard(x, axes):
    return x


def _pipelined_stack(layers_staged, h, mesh, n_stage: int,
                     num_microbatches: int, shard, attention="gspmd",
                     remat=False):
    """GPipe over the 'pipe' axis of `mesh`, differentiable.

    layers_staged leaves: (n_stage, per_stage, ...), stage dim sharded
    on 'pipe'. Only 'pipe' is manual; the stage body stays GSPMD so the
    dp/tp/sp/ep shardings inside _layer keep working."""
    def local_fn(sparams, hloc):
        sparams = jax.tree.map(lambda v: v[0], sparams)
        idx = jax.lax.axis_index("pipe")
        B = hloc.shape[0]
        mb = B // num_microbatches
        micro = hloc.reshape((num_microbatches, mb) + hloc.shape[1:])
        n_tick = num_microbatches + n_stage - 1
        buf = jnp.zeros((mb,) + hloc.shape[1:], hloc.dtype)
        outs = jnp.zeros_like(micro)
        perm = [(j, (j + 1) % n_stage) for j in range(n_stage)]

        # prevent_cse=False: under lax.scan the problematic CSE cannot
        # occur and the default optimization barriers would only block
        # XLA fusion (the jax-recommended scan+checkpoint setting)
        layer_fn = (jax.checkpoint(_layer, prevent_cse=False,
                                   static_argnums=(2, 3))
                    if remat else _layer)

        def stage_body(hc, lp):
            return layer_fn(lp, hc, shard, attention), None

        def tick(carry, t):
            buf, outs = carry
            feed = micro[jnp.clip(t, 0, num_microbatches - 1)]
            h_in = jnp.where(idx == 0,
                             jnp.where(t < num_microbatches, feed, buf),
                             buf)
            h_out, _ = jax.lax.scan(stage_body, h_in, sparams)
            out_t = t - (n_stage - 1)
            emit = jnp.logical_and(idx == n_stage - 1, out_t >= 0)
            oi = jnp.clip(out_t, 0, num_microbatches - 1)
            outs = outs.at[oi].set(jnp.where(emit, h_out, outs[oi]))
            buf = jax.lax.ppermute(h_out, "pipe", perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(n_tick))
        outs = jnp.where(idx == n_stage - 1, outs,
                         jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pipe")
        return outs.reshape((B,) + hloc.shape[1:])

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pipe"), layers_staged), P()),
        out_specs=P(), axis_names={"pipe"}, check_vma=False,
    )(layers_staged, h)


def _lm_head_loss(params, h, labels, shard):
    h = _rmsnorm(h, params["ln_f"])
    logits = jnp.einsum("btd,dv->btv", h, params["head"])
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return jnp.mean(nll)


def pipeline_lm_loss(params_staged, tokens, labels, mesh, n_stage: int,
                     num_microbatches: int, attention: str = "gspmd",
                     remat: bool = False):
    """Mean NLL of the pipelined model. params_staged: stage layout.
    remat=True checkpoints each LAYER inside the stage scan (the
    classic scan-over-layers rematerialization): activation memory per
    stage drops from O(layers) to O(1) at the cost of one extra
    forward in the backward."""
    shard = _mesh_shard(mesh)
    h = params_staged["embed"][tokens]
    h = shard(h, ("data", "seq", None))
    h = _pipelined_stack(params_staged["layers"], h, mesh, n_stage,
                         num_microbatches, shard, attention=attention,
                         remat=remat)
    return _lm_head_loss(params_staged, h, labels, shard)


def dense_lm_logits(params, tokens):
    """Full-forward next-token logits (B, T, V) of the dense reference
    stack — identical math to :func:`dense_lm_loss` without the loss.
    This is the serving oracle: mxnet_tpu/serve2's paged-KV continuous-
    batching decode must reproduce these logits (and their greedy argmax
    trajectory) within the online-softmax tolerance class."""
    h = params["embed"][tokens]

    def body(hc, lp):
        return _layer(lp, hc, _no_shard), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = _rmsnorm(h, params["ln_f"])
    return jnp.einsum("btd,dv->btv", h, params["head"])


def dense_lm_loss(params, tokens, labels):
    """Single-device reference: identical math, plain scan over all L
    layers, no mesh, no collectives. The pipelined loss/gradients must
    match this numerically — the same oracle style the dp/tp/sp/ep
    dryrun already uses."""
    h = params["embed"][tokens]

    def body(hc, lp):
        return _layer(lp, hc, _no_shard), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    return _lm_head_loss(params, h, labels, _no_shard)


# ---------------------------------------------------------------------------
# training step
# ---------------------------------------------------------------------------

def build_pipeline_lm_step(mesh: Mesh, n_stage: int,
                           num_microbatches: int, lr: float = 1e-3,
                           attention: str = "gspmd",
                           remat: bool = False):
    """Returns (step, in_shardings) where step(params_staged, opt_state,
    tokens, labels) -> (params_staged, opt_state, loss) is one jitted
    XLA program: pipelined forward, backward through the GPipe schedule,
    Adam update. Callers can .lower(...) the returned function to
    inspect the compiled HLO's collectives (see parallel/hlo_check.py)."""
    pspec = pipeline_lm_shardings(mesh, n_stage)
    dspec = NamedSharding(mesh, P("data", "seq"))

    def step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(pipeline_lm_loss)(
            params, tokens, labels, mesh, n_stage, num_microbatches,
            attention, remat)
        new_params, new_opt = adam_apply(params, grads, opt_state, lr=lr)
        return new_params, new_opt, loss

    ospec = {"mean": pspec, "var": pspec,
             "t": NamedSharding(mesh, P())}
    jitted = jax.jit(step, donate_argnums=(0, 1),
                     in_shardings=(pspec, ospec, dspec, dspec),
                     out_shardings=(pspec, ospec, None))
    return jitted, (pspec, ospec, dspec)


# ---------------------------------------------------------------------------
# the shared oracle (driver dryrun + tests run the SAME checks)
# ---------------------------------------------------------------------------

def combined_mesh_drill(mesh: Mesh, *, num_microbatches: int = 2,
                        lr: float = 1e-3, n_steps: int = 2,
                        seed: int = 0, data_seed: int = 11,
                        rtol: float = 2e-4, attention: str = "gspmd"):
    """End-to-end verification of the five-axis composition on `mesh`
    (axes 'data'/'model'/'seq'/'pipe'; ep rides 'model'):

    1. an n_steps Adam trajectory through the pipelined step must match
       the dense single-device reference numerically;
    2. the compiled HLO must contain the expected collectives on each
       active mesh axis, and every collective's replica groups must
       match SOME axis subset (no unexplained communication).

    Returns (counts, dense_traj, pipe_traj). Used verbatim by both the
    driver's dryrun (__graft_entry__._combined_mesh_drill) and
    tests/nightly/combined_mesh_worker.py so the two cannot drift.
    """
    from .hlo_check import collective_report, summarize

    dp, tp = mesh.shape["data"], mesh.shape["model"]
    sp, pp = mesh.shape["seq"], mesh.shape["pipe"]
    V = 64
    params = init_pipeline_lm(seed, vocab=V, d_model=16,
                              n_layers=2 * pp, n_heads=4, d_head=4,
                              d_ff=32, n_experts=2)
    rs = onp.random.RandomState(data_seed)
    B, T = 2 * max(dp, num_microbatches), 8 * sp
    tokens = jnp.asarray(rs.randint(0, V, (B, T)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, V, (B, T)), jnp.int32)

    @jax.jit
    def dense_step(p, o, t, l):
        loss, g = jax.value_and_grad(dense_lm_loss)(p, t, l)
        p2, o2 = adam_apply(p, g, o, lr=lr)
        return p2, o2, loss

    dpar, dopt = params, adam_init(params)
    dense_traj = []
    for _ in range(n_steps):
        dpar, dopt, lo = dense_step(dpar, dopt, tokens, labels)
        dense_traj.append(float(lo))

    staged = stage_params(params, pp)
    step, (pspec, ospec, dspec) = build_pipeline_lm_step(
        mesh, pp, num_microbatches, lr=lr, attention=attention)
    ppar = jax.device_put(staged, pspec)
    popt = jax.tree.map(lambda v, s: jax.device_put(v, s),
                        adam_init(staged), ospec)
    tok = jax.device_put(tokens, dspec)
    lab = jax.device_put(labels, dspec)
    compiled = step.lower(ppar, popt, tok, lab).compile()

    pipe_traj = []
    for _ in range(n_steps):
        ppar, popt, lo = compiled(ppar, popt, tok, lab)
        pipe_traj.append(float(lo))
    for got, want in zip(pipe_traj, dense_traj):
        assert abs(got - want) <= rtol * max(1.0, abs(want)), \
            (f"combined dp{dp}xtp{tp}xsp{sp}xpipe{pp} trajectory "
             f"diverged: {pipe_traj} vs {dense_traj}")

    report = collective_report(compiled.as_text(), mesh)
    counts = summarize(report)

    def has(op, axis):
        return any(i.op == op and i.axes and axis in i.axes
                   for i in report)

    if dp > 1:
        assert has("all-reduce", "data"), \
            f"no data-axis grad all-reduce: {counts}"
    if pp > 1:
        assert has("collective-permute", "pipe"), \
            f"no pipe ppermute: {counts}"
    if tp > 1:
        assert any(has(op, "model") for op in
                   ("all-reduce", "reduce-scatter", "all-gather")), \
            f"no model-axis (tp/ep) collective: {counts}"
    if sp > 1:
        assert any(has(op, "seq") for op in
                   ("all-gather", "all-to-all", "all-reduce",
                    "collective-permute")), \
            f"no seq-axis collective: {counts}"
    unmatched = [i for i in report if i.axes is None]
    assert not unmatched, \
        ("collectives matching no mesh-axis pattern: "
         f"{[i.line[:120] for i in unmatched]}")
    return counts, dense_traj, pipe_traj
