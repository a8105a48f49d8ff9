"""mxpipe: pipeline parallelism as a ShardPlan axis (ISSUE 19).

Tier-1 fast cut — schedules as data (tick counts, bubble math,
dependency order under a fake clock, in-flight bounds), 1F1B/GPipe
training parity against the monolithic dense oracle with ZERO
steady-state recompiles, the stage-kind program census, transfer-rung
bookkeeping, PipePlan spec composition + manifest round-trip, the
save-at-4→restore-at-2 re-stage contract, in-process stage remap, and
the pipelint findings contract (clean pipeline clean, bad fixtures
fire).

The subprocess lost-stage drill (SIGKILL a mid-pipeline host; the
survivors remap stages, redo from committed state, and land on the
baseline loss bit-for-bit) is @slow.
"""
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401 — jax compat shims
import jax
import jax.numpy as jnp

from mxnet_tpu import config
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.pipeline_lm import (dense_lm_loss,
                                            init_pipeline_lm,
                                            stage_params,
                                            unstage_params)
from mxnet_tpu.parallel.train import adam_apply, adam_init
from mxnet_tpu.pipe import (LMStageModel, PipePlan, PipeStepFunction,
                            build_schedule, gpipe, one_f_one_b)
from mxnet_tpu.pipe.stepfn import PIPE_TOL_REL
from mxnet_tpu.pipe.transfer import LocalTransport

VOCAB, D, L = 32, 16, 4


def _params(seed=0, n_layers=L):
    return init_pipeline_lm(seed, vocab=VOCAB, d_model=D,
                            n_layers=n_layers, n_heads=2, d_head=8,
                            d_ff=32, n_experts=2)


def _batch(step, b=8, t=6):
    r = onp.random.RandomState(1000 + step)
    return (jnp.asarray(r.randint(0, VOCAB, size=(b, t)), dtype="int32"),
            jnp.asarray(r.randint(0, VOCAB, size=(b, t)), dtype="int32"))


# ---------------------------------------------------------------------------
# schedules as data
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
@pytest.mark.parametrize("S,M", [(1, 1), (2, 4), (3, 3), (4, 8)])
def test_schedule_tick_count_and_bubble(kind, S, M):
    s = build_schedule(kind, S, M)
    assert s.n_ticks == 2 * (M + S - 1)
    assert s.bubble_fraction() == pytest.approx((S - 1) / (M + S - 1))
    s.validate()  # raises on any dependency violation
    d = s.describe()
    assert d["kind"] == kind and d["n_ticks"] == s.n_ticks


@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
def test_schedule_dependency_order_fake_clock(kind):
    """Walk the tick program with a fake clock and re-prove the
    dependency order item by item: F(s,m) needs F(s-1,m) done, B(s,m)
    needs F(s,m) and B(s+1,m) done, every (stage, micro) runs each
    phase exactly once."""
    S, M = 4, 6
    sched = build_schedule(kind, S, M)
    done_f, done_b = set(), set()
    for tick, item in sched.items():
        if item.phase == "F":
            if item.stage > 0:
                assert (item.stage - 1, item.micro) in done_f, \
                    (tick, item)
            assert (item.stage, item.micro) not in done_f
            done_f.add((item.stage, item.micro))
        else:
            assert (item.stage, item.micro) in done_f, (tick, item)
            if item.stage < S - 1:
                assert (item.stage + 1, item.micro) in done_b, \
                    (tick, item)
            assert (item.stage, item.micro) not in done_b
            done_b.add((item.stage, item.micro))
    assert len(done_f) == len(done_b) == S * M


def test_schedule_in_flight_bounds():
    """The 1F1B memory claim: stage s never holds more than
    min(M, S-s) forwarded-not-yet-backwarded microbatches; GPipe
    holds up to M."""
    S, M = 4, 8
    for kind, bound in (("1f1b", lambda s: min(M, S - s)),
                        ("gpipe", lambda s: M)):
        sched = build_schedule(kind, S, M)
        live = {s: 0 for s in range(S)}
        peak = {s: 0 for s in range(S)}
        for _, it in sched.items():
            live[it.stage] += 1 if it.phase == "F" else -1
            peak[it.stage] = max(peak[it.stage], live[it.stage])
        for s in range(S):
            assert peak[s] <= bound(s), (kind, s, peak)
            assert sched.max_in_flight(s) == peak[s], (kind, s)
        if kind == "1f1b" and M > S:
            # the bound is strictly better than GPipe's somewhere
            assert peak[0] < M


def test_schedule_bad_inputs():
    with pytest.raises(MXNetError):
        build_schedule("interleaved", 2, 4)
    with pytest.raises(MXNetError):
        build_schedule("gpipe", 0, 4)
    with pytest.raises(MXNetError):
        one_f_one_b(2, 0)
    assert gpipe(2, 4).kind == "gpipe"


# ---------------------------------------------------------------------------
# training parity vs the monolithic oracle
# ---------------------------------------------------------------------------

def _oracle_losses(params, lr, steps):
    """The un-pipelined reference: plain value_and_grad over the dense
    LM + the same adam — the trajectory every pipelined run must
    reproduce."""
    st = adam_init(params)
    vg = jax.jit(jax.value_and_grad(dense_lm_loss))
    out = []
    for i in range(steps):
        tok, lab = _batch(i)
        loss, g = vg(params, tok, lab)
        params, st = adam_apply(params, g, st, lr=lr)
        out.append(float(loss))
    return out, params


@pytest.mark.parametrize("kind,S", [("1f1b", 2), ("1f1b", 4),
                                    ("gpipe", 2), ("gpipe", 4)])
def test_pipeline_parity_and_closed_cache(kind, S):
    """The acceptance gate: pipelined training (S stages, 4
    microbatches) matches the monolithic oracle within the declared
    tolerance class (bitwise on CPU in practice) AND compiles nothing
    after the warmup step."""
    lr, steps = 1e-3, 3
    ref_losses, ref_params = _oracle_losses(_params(), lr, steps)
    sf = PipeStepFunction(_params(), n_stage=S, schedule=kind,
                          n_microbatch=4, lr=lr, name=f"t-{kind}{S}")
    got = []
    for i in range(steps):
        tok, lab = _batch(i)
        got.append(sf.step(tok, lab))
    for a, b in zip(got, ref_losses):
        assert abs(a - b) / max(abs(b), 1e-9) <= PIPE_TOL_REL, \
            (kind, S, got, ref_losses)
    # the updated weights agree too, not just the scalar loss. Adam
    # turns reassociation-level grad noise into up-to-lr-sized updates
    # (m/sqrt(v) is ±1 for tiny grads), so the weight tolerance is a
    # few lr steps, not PIPE_TOL_REL
    dense = sf.dense_params()
    ref_flat = jax.tree.leaves(ref_params)
    got_flat = jax.tree.leaves(dense)
    for r, g in zip(ref_flat, got_flat):
        assert onp.allclose(onp.asarray(r), onp.asarray(g),
                            rtol=PIPE_TOL_REL, atol=5 * lr)
    rep = sf.lint_report()
    assert rep["recompiles_after_warmup"] == 0, rep
    assert rep["warmed"] is True


def test_program_census_by_stage_kind():
    """Programs are compiled per stage KIND: S=4 compiles first/mid/
    last grad programs (2+2+1) and one update program per kind."""
    sf = PipeStepFunction(_params(), n_stage=4, n_microbatch=4,
                          name="t-census")
    tok, lab = _batch(0)
    sf.step(tok, lab)
    census = sf.program_census()
    assert census == {"fwd_first": 1, "fwd_mid": 1, "loss_grad": 1,
                      "bwd_mid": 1, "bwd_first": 1, "update": 3}, census
    assert sf.program_counts() == {"grad": 5, "update": 3,
                                   "total": 8}


def test_microbatch_divisibility_raises():
    sf = PipeStepFunction(_params(), n_stage=2, n_microbatch=4,
                          name="t-div")
    tok, lab = _batch(0, b=6)  # 6 % 4 != 0
    with pytest.raises(MXNetError):
        sf.step(tok, lab)


def test_stage_count_must_divide_layers():
    with pytest.raises(MXNetError):
        PipeStepFunction(_params(), n_stage=3, name="t-odd")


# ---------------------------------------------------------------------------
# transfers: rung bookkeeping
# ---------------------------------------------------------------------------

def test_local_transport_rungs_and_roundtrip():
    t = LocalTransport("t-rungs")
    t.rungs.declare("act", (2, 6, D), "float32")
    x = jnp.ones((2, 6, D), "float32")
    y = t.send_recv("act|n0|e0-1|m0", x)
    assert y is x
    rep = t.lint_report()
    assert rep["declared_rungs"] == [("act", (2, 6, D), "float32")]
    assert rep["warmed_rungs"] == [("act", (2, 6, D), "float32")]
    with pytest.raises(MXNetError):
        t.send_recv("act|n0|e0-1|m1", None)


# ---------------------------------------------------------------------------
# PipePlan: specs, manifest, re-stage
# ---------------------------------------------------------------------------

def test_pipeplan_mesh_stage_specs():
    # conftest forces 8 CPU devices: pipe=2 leaves n_batch=4, and 8
    # layers staged into 2 give per-stage slabs of 4 (divisible by 4)
    plan = PipePlan(n_stage=2, axes={"batch": -1, "pipe": 2})
    assert plan.mesh_stage
    staged = stage_params(_params(n_layers=8), 2)
    wq = staged["layers"]["wqkv"]
    assert tuple(plan.param_spec("layers.wqkv", wq).spec) == ("pipe",)
    # ZeRO composes PER STAGE: dim 0 stays staged, dim 1 shards batch
    sspec = tuple(plan.state_spec("layers.wqkv", wq).spec)
    assert sspec[0] == "pipe" and sspec[1] == "batch"
    # unstaged leaves fall through to plain ShardPlan behavior
    assert tuple(plan.param_spec("embed", _params()["embed"]).spec) == ()
    # a staged name whose leading dim is not n_stage is a hard error
    with pytest.raises(MXNetError):
        plan.param_spec("layers.wqkv", _params()["layers"]["wqkv"])


def test_pipeplan_manifest_roundtrip_and_dispatch():
    from mxnet_tpu.shard.plan import ShardPlan
    plan = PipePlan(n_stage=4, axes={"batch": -1}, schedule="gpipe",
                    n_microbatch=8)
    desc = json.loads(json.dumps(plan.describe()))  # wire round-trip
    back = ShardPlan.from_manifest(desc)
    assert isinstance(back, PipePlan)
    assert (back.n_stage, back.schedule, back.n_microbatch) == \
        (4, "gpipe", 8)
    assert back.describe() == plan.describe()
    # explicit stage-count override beats the recorded value
    two = PipePlan.from_manifest(desc, n_stage=2)
    assert two.n_stage == 2
    # ...and MXPIPE_STAGES beats the recorded value too
    old = os.environ.get("MXPIPE_STAGES")
    os.environ["MXPIPE_STAGES"] = "2"
    try:
        assert PipePlan.from_manifest(desc).n_stage == 2
    finally:
        if old is None:
            os.environ.pop("MXPIPE_STAGES", None)
        else:
            os.environ["MXPIPE_STAGES"] = old


def test_restage_leaf_math():
    staged = stage_params(_params(), 4)
    v = staged["layers"]["w1"]
    re2 = PipePlan.restage_leaf(v, 2)
    assert re2.shape[0] == 2 and re2.shape[1] == v.shape[1] * 2
    assert onp.allclose(
        re2.reshape((-1,) + v.shape[2:]),
        v.reshape((-1,) + v.shape[2:]))
    with pytest.raises(MXNetError):
        PipePlan.restage_leaf(v, 3)  # 4 layers don't split into 3
    with pytest.raises(MXNetError):
        PipePlan.restage_leaf(jnp.ones((4,)), 2)


def test_save_at_4_restore_at_2_continues_trajectory():
    """The stage-count-independent checkpoint contract: train 2 steps
    at 4 stages, snapshot DENSE (params + adam state + manifest),
    restore into a 2-stage pipeline, and the continued trajectory
    matches a never-interrupted 4-stage run step for step."""
    lr = 1e-3
    sf4 = PipeStepFunction(_params(), n_stage=4, n_microbatch=4,
                           lr=lr, name="t-save4")
    for i in range(2):
        sf4.step(*_batch(i))
    snap = {"params": jax.tree.map(onp.asarray, sf4.dense_params()),
            "opt": jax.tree.map(onp.asarray, sf4.dense_opt()),
            "plan": PipePlan(n_stage=4, axes={"batch": -1}).describe()}
    # the uninterrupted reference continues at 4 stages
    ref = [sf4.step(*_batch(i)) for i in range(2, 4)]
    # restore at 2 stages from the dense snapshot
    plan2 = PipePlan.from_manifest(snap["plan"], n_stage=2)
    assert plan2.n_stage == 2
    sf2 = PipeStepFunction(_params(), n_stage=2, n_microbatch=4,
                           lr=lr, name="t-restore2")
    sf2.load_dense(jax.tree.map(jnp.asarray, snap["params"]),
                   jax.tree.map(jnp.asarray, snap["opt"]))
    got = [sf2.step(*_batch(i)) for i in range(2, 4)]
    for a, b in zip(got, ref):
        assert abs(a - b) / max(abs(b), 1e-9) <= PIPE_TOL_REL, \
            (got, ref)


def test_in_process_stage_remap_callback():
    """_remap is a pure function of the (sorted) worker list: the
    stage map covers every stage with survivors only, and the
    on_restage callback fires exactly when the world changes."""
    calls = []
    sf = PipeStepFunction(_params(), n_stage=4, n_microbatch=4,
                          name="t-remap",
                          on_restage=lambda m, t: calls.append((m, t)))
    # local (no session): single pseudo-worker owns every stage
    assert set(sf.stage_map) == {0, 1, 2, 3}
    assert len(set(sf.stage_map.values())) == 1
    assert calls == []  # the initial map is not a REmap


# ---------------------------------------------------------------------------
# pipelint
# ---------------------------------------------------------------------------

def test_pipelint_clean_pipeline_is_clean():
    from mxnet_tpu.passes.pipelint import lint_pipe_report
    sf = PipeStepFunction(_params(), n_stage=2, n_microbatch=4,
                          name="t-lint")
    sf.step(*_batch(0))
    findings = lint_pipe_report(sf.lint_report())
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, errors
    # the informational bubble note is always present
    assert any(f.check == "bubble-fraction" for f in findings)


def test_pipelint_fires_on_bad_fixtures():
    from mxnet_tpu.passes.pipelint import lint_pipe_report
    bad = {"name": "<bad>", "schedule": "1f1b", "n_stage": 2,
           "n_micro": 3, "batch": 8, "warmed": True,
           "bubble_fraction": 0.25,
           "stage_param_bytes": [100, 100000],
           "declared_rungs": [("act", (2, 6, 16), "float32")],
           "warmed_rungs": [("act", (5, 6, 16), "float32")],
           "recompiles_after_warmup": 2,
           "stage_map": {0: "w0"}, "world": 1, "programs": {}}
    fired = {f.check for f in lint_pipe_report(bad)}
    for check in ("stage-imbalance", "microbatch-not-divisible",
                  "unwarmed-transfer-rungs", "off-rung-transfer",
                  "recompile-after-warmup", "stage-map-hole"):
        assert check in fired, (check, fired)


def test_pipelint_registered_in_default_manager():
    from mxnet_tpu.passes import default_manager
    assert "pipelint" in default_manager().names()


def test_unstage_params_inverse():
    p = _params()
    staged = stage_params(p, 2)
    back = unstage_params(staged)
    for a, b in zip(jax.tree.leaves(p["layers"]),
                    jax.tree.leaves(back["layers"])):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))


def test_stage_model_split_merge_roundtrip():
    m = LMStageModel()
    p = _params()
    stages = m.split(p, 4)
    assert len(stages) == 4
    assert "embed" in stages[0] and "embed" not in stages[1]
    assert "head" in stages[-1] and "ln_f" in stages[-1]
    merged = m.merge(stages)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(merged)):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))


# ---------------------------------------------------------------------------
# the subprocess lost-stage drill (@slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lost_stage_drill_subprocess():
    """SIGKILL a mid-pipeline stage host mid-run: survivors detect the
    dead stage via missed beats, remap stages onto the survivor set,
    redo the interrupted step from committed state, land on the
    uninterrupted baseline's loss within MXELASTIC_LOSS_TOL (0.0
    measured — bit-identical), and compile nothing beyond the audited
    re-stage budget."""
    from mxnet_tpu.pipe.drill import run_pipe_drill
    base = run_pipe_drill(n_hosts=3, steps=8, step_sleep=0.01)
    rep = run_pipe_drill(n_hosts=3, steps=8, kill_step=3, kill_rank=1,
                         baseline_loss=base["final_loss"],
                         step_sleep=0.01)
    assert rep["world_after_kill"] == 2
    assert rep["recompiles_beyond_budget"] == 0, rep["rekeys"]
    tol = float(config.get("MXELASTIC_LOSS_TOL"))
    assert rep["loss_delta"] is not None and rep["loss_delta"] <= tol
    # the dead host owns nothing afterwards; all stages covered
    fmap = rep["stage_map_after_kill"]
    assert sorted(int(s) for s in fmap) == [0, 1, 2]
    assert "w1" not in fmap.values()
