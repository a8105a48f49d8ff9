"""The measurement runner: candidates in, legal measured objectives out.

Drives the existing bench harnesses **in-process** at a candidate
config — the fused train step (symbol mode, so ``MXNET_GRAPH_OPT``
participates) and the serve2 open-loop loadgen — reading objectives
from wall-clock medians plus the telemetry registry, and enforcing the
two legality rails as **hard gates, never search dimensions**:

1. **closed cache** — a candidate whose steady state recompiles after
   warmup is rejected (``recompile-after-warmup``), whatever its
   measured time: a recompiling config's bench number is a lie about
   production behavior (the recompile auditor's count is the witness);
2. **tolerance class** — a candidate whose results diverge from the
   defaults run beyond its opt/verify tolerance class is rejected
   (``tolerance-breach``): profitability search must never buy speed
   with silent numerics drift. Bitwise-class candidates must match
   bitwise; fusion/layout/quant classes get their calibrated bands
   (``mxnet_tpu/opt/verify.py``).

:func:`run_search` is the loop: measure the defaults (the baseline is
trial 0 — "tuned" can therefore never be *worse* than defaults in the
DB), sample the space while the cost model is cold, and once it warms
rank a candidate pool and spend real measurements on the predicted
frontier (with a periodic exploration trial so the model keeps seeing
off-frontier evidence). Every legal measurement is appended to the
tuning DB with provenance.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as onp

from ..base import MXNetError, get_logger
from .db import SCHEMA_VERSION, TuneDB
from .model import CostModel
from .space import KnobSpace, objective_direction

__all__ = ["MeasureResult", "measure_candidate", "scoped_config",
           "fused_step_bench_fn", "serve2_bench_fn", "run_search"]

_log = get_logger("mxnet_tpu.tune")

#: legality-rail rejection reasons (tunelint cross-references these)
REJECT_RECOMPILE = "recompile-after-warmup"
REJECT_TOLERANCE = "tolerance-breach"
REJECT_NO_VALUE = "no-measurement"


@contextlib.contextmanager
def scoped_config(cfg: Dict[str, object]):
    """Apply a candidate via ``config.set_flag`` and restore the
    caller's overrides on exit (an env-only or default value
    re-resolves after the unset)."""
    from .. import config
    saved = {}
    try:
        for name, value in cfg.items():
            saved[name] = config._OVERRIDES.get(name, _MISSING) \
                if hasattr(config, "_OVERRIDES") else _MISSING
            config.set_flag(name, value)
        yield
    finally:
        for name, prev in saved.items():
            if prev is _MISSING:
                config.unset_flag(name)
            else:
                config.set_flag(name, prev)


class _Missing:
    pass


_MISSING = _Missing()


class MeasureResult:
    """One candidate's outcome: the objective value when legal, the
    rail that rejected it otherwise."""

    __slots__ = ("config", "objective", "value", "ok", "reject",
                 "extra")

    def __init__(self, config, objective, value, ok, reject=None,
                 extra=None):
        self.config = dict(config)
        self.objective = objective
        self.value = value
        self.ok = bool(ok)
        self.reject = reject
        self.extra = dict(extra or {})

    def to_dict(self) -> dict:
        return {"config": self.config, "objective": self.objective,
                "value": self.value, "ok": self.ok,
                "reject": self.reject, "extra": self.extra}

    def __repr__(self):
        tag = "ok" if self.ok else f"REJECTED({self.reject})"
        return (f"MeasureResult({self.objective}={self.value} {tag} "
                f"@ {self.config})")


def measure_candidate(space: KnobSpace, cfg: Dict[str, object],
                      bench_fn: Callable[[Dict], Dict],
                      objective: str) -> MeasureResult:
    """Validate ``cfg`` against the space, run ``bench_fn`` at it, and
    apply the legality rails to the returned report.

    ``bench_fn(cfg) -> dict`` must report at least ``value`` and
    ``recompiles_after_warmup``; ``tolerance_ok``/``tolerance_rel``/
    ``tolerance_class`` when the candidate can move numerics."""
    objective_direction(objective)
    cfg = space.validate(cfg)
    rep = bench_fn(cfg)
    extra = {k: v for k, v in rep.items() if k != "value"}
    recompiles = int(rep.get("recompiles_after_warmup", 0) or 0)
    if recompiles > 0:
        return MeasureResult(cfg, objective, None, False,
                             REJECT_RECOMPILE, extra)
    if rep.get("tolerance_ok") is False:
        return MeasureResult(cfg, objective, None, False,
                             REJECT_TOLERANCE, extra)
    value = rep.get("value")
    if value is None:
        return MeasureResult(cfg, objective, None, False,
                             REJECT_NO_VALUE, extra)
    return MeasureResult(cfg, objective, float(value), True, None,
                         extra)


# ---------------------------------------------------------------------------
# in-process bench harnesses
# ---------------------------------------------------------------------------

def _conv_loss_symbol(batch: int):
    """Small conv+bn+relu net under a regression head — the workload
    whose level-2 fusion/layout rewrites change the program most."""
    from .. import sym
    n = sym.var("data")
    for i, nf in enumerate((16, 32)):
        n = sym.Convolution(n, kernel=(3, 3), num_filter=nf,
                            pad=(1, 1), name=f"tc{i}")
        n = sym.BatchNorm(n, name=f"tbn{i}")
        n = sym.Activation(n, act_type="relu", name=f"tr{i}")
        n = sym.Pooling(n, kernel=(2, 2), stride=(2, 2),
                        pool_type="max", name=f"tp{i}")
    n = sym.Flatten(n)
    n = sym.FullyConnected(n, num_hidden=32, name="tfc1")
    n = sym.Activation(n, act_type="relu", name="tfa")
    n = sym.FullyConnected(n, num_hidden=8, name="tfc2")
    loss = sym.LinearRegressionOutput(n, sym.var("label"), name="tlro")
    return loss, {"data": (batch, 3, 24, 24), "label": (batch, 8)}


def fused_step_bench_fn(batch: int = 8, warmup: int = 2,
                        steps: int = 6, seed: int = 0,
                        loss_tol_floor: float = 5e-3
                        ) -> Callable[[Dict], Dict]:
    """Build the fused-train-step harness; the returned callable
    measures one candidate (objective: median step seconds, lower
    better). The first call measures the *defaults* and caches their
    loss trajectory as the parity reference for the tolerance rail."""
    from .. import nd, telemetry
    from ..opt.verify import random_value_map, tolerance_for
    from ..step import StepFunction

    loss_sym, shapes = _conv_loss_symbol(batch)
    vals = random_value_map(loss_sym, shapes, seed=seed)
    arg_names = set(loss_sym.list_arguments())
    aux_names = set(loss_sym.list_auxiliary_states())
    rs = onp.random.RandomState(seed + 1)
    batches = [(nd.array(rs.uniform(-1, 1, shapes["data"])
                         .astype("float32")),
                nd.array(rs.uniform(-1, 1, shapes["label"])
                         .astype("float32")))
               for _ in range(max(2, warmup))]
    state = {"baseline_losses": None}

    def bench(cfg: Dict) -> Dict:
        with scoped_config(cfg):
            args = {k: nd.array(vals[k]) for k in arg_names
                    if k not in ("data", "label")}
            aux = {k: nd.array(vals[k]) for k in aux_names}
            fused = StepFunction(
                loss_sym, arg_dict=args, aux_dict=aux,
                input_names=("data", "label"), optimizer="sgd",
                optimizer_params={"learning_rate": 0.01})
            losses = []
            for i in range(warmup):
                x, y = batches[i % len(batches)]
                losses.append(float(fused.step(x, y).asnumpy()
                                    .mean()))
            rc0 = telemetry.recompile_count()
            times = []
            for i in range(steps):
                x, y = batches[i % len(batches)]
                t0 = time.perf_counter()
                loss = fused.step(x, y)
                losses.append(float(loss.asnumpy().mean()))
                times.append(time.perf_counter() - t0)
            recompiles = telemetry.recompile_count() - rc0
            rep = fused.opt_report
            tol_class = rep.tolerance_class if rep else "bitwise"
        if state["baseline_losses"] is None:
            # first call IS the defaults run: it defines parity
            state["baseline_losses"] = losses
            tol_ok, tol_rel = True, 0.0
        else:
            base = onp.asarray(state["baseline_losses"])
            cand = onp.asarray(losses)
            denom = max(float(onp.abs(base).max()), 1e-9)
            tol_rel = float(onp.abs(cand - base).max()) / denom
            rtol, _ = tolerance_for(tol_class)
            # trajectory error accumulates across steps; the band is
            # the class rtol with generous headroom, floored so the
            # bitwise class still tolerates nothing but noise-free
            # equality paths (exact on one backend)
            band = max(rtol * 100.0, loss_tol_floor
                       if tol_class != "bitwise" else 0.0)
            tol_ok = tol_rel <= band
        ts = sorted(times)
        return {"value": ts[len(ts) // 2],
                "recompiles_after_warmup": int(recompiles),
                "tolerance_class": tol_class,
                "tolerance_rel": tol_rel, "tolerance_ok": tol_ok,
                "final_loss": losses[-1], "steps": steps,
                "batch": batch}

    return bench


def serve2_bench_fn(requests: int = 12, max_new: int = 8,
                    prompt_len: int = 12, qps: float = 4.0,
                    slo_ms: float = 4000.0, seed: int = 0,
                    d_model: int = 32, n_layers: int = 2
                    ) -> Callable[[Dict], Dict]:
    """serve2 open-loop harness; objective: goodput QPS within the SLO
    (higher better). Knobs land via flags so the engine's own
    resolution order (kwarg > tuned > flag) is what gets measured."""
    from .. import telemetry
    from ..parallel.pipeline_lm import init_pipeline_lm
    from ..serve.loadgen import run_loadgen_open
    from ..serve2 import DecodeEngine

    vocab = 64
    params = init_pipeline_lm(seed, vocab=vocab, d_model=d_model,
                              n_layers=n_layers, n_heads=2,
                              d_head=d_model // 2, d_ff=2 * d_model,
                              n_experts=2)
    rs = onp.random.RandomState(seed)
    prompts = [rs.randint(1, vocab, size=(prompt_len,)).astype("int32")
               for _ in range(requests)]

    def bench(cfg: Dict) -> Dict:
        with scoped_config(cfg):
            eng = DecodeEngine(params, max_new_default=max_new,
                               name="mxtune-probe")
            try:
                eng.warmup()
                eng.predict(prompts[0])  # end-to-end warm pass
                rc0 = telemetry.recompile_count()
                res = run_loadgen_open(
                    lambda p: eng.predict(p), prompts, qps=qps,
                    concurrency=8, seed=seed)
                recompiles = telemetry.recompile_count() - rc0
            finally:
                eng.close()
        within = sum(1 for l in res["latencies_s"]
                     if l * 1000.0 <= slo_ms)
        goodput = within / res["wall_s"]
        return {"value": goodput,
                "recompiles_after_warmup": int(recompiles),
                "tolerance_ok": not res["errors"],
                "tolerance_class": "serving-errors",
                "p99_ms": res["p99_ms"], "p50_ms": res["p50_ms"],
                "achieved_qps": res["achieved_qps"],
                "errors": len(res["errors"]),
                "requests": requests, "slo_ms": slo_ms}

    return bench


# ---------------------------------------------------------------------------
# the search loop
# ---------------------------------------------------------------------------

def run_search(space: KnobSpace, bench_fn: Callable[[Dict], Dict],
               objective: str, budget: Optional[int] = None,
               seed: int = 0, db: Optional[TuneDB] = None,
               key: Optional[Dict] = None,
               extra_features: Optional[List[float]] = None,
               pool: int = 24, explore_every: int = 4,
               model: Optional[CostModel] = None,
               source: str = "mxtune", log: bool = True) -> Dict:
    """Model-pruned search over ``space``; returns the search report
    and (when ``db``+``key`` are given) persists every legal
    measurement with provenance.

    Internally every objective is direction-normalized to *smaller is
    better*; the report converts back. ``extra_features`` (e.g.
    ``cost_analysis`` HLO stats) are appended to every feature row."""
    from .. import config
    direction = objective_direction(objective)
    sgn = 1.0 if direction == "min" else -1.0
    if budget is None:
        budget = int(config.get("MXTUNE_BUDGET"))
    rng = onp.random.RandomState(seed)
    xf = list(extra_features or [])

    def feats(cfg):
        return space.features(cfg) + xf

    def persist(res: MeasureResult, role: str, trial: int):
        if db is None or key is None or not res.ok:
            return
        db.append({
            "key": key, "config": res.config,
            "objective": objective, "value": res.value,
            "ok": True,
            "provenance": {"source": source, "role": role,
                           "trial": trial,
                           "bench_schema": SCHEMA_VERSION,
                           "direction": direction,
                           "tolerance_class":
                               res.extra.get("tolerance_class"),
                           "recompiles_after_warmup": 0}})

    baseline = measure_candidate(space, {}, bench_fn, objective)
    if not baseline.ok:
        raise MXNetError(
            f"the DEFAULTS config failed the legality rails "
            f"({baseline.reject}) — the harness itself is broken; "
            "nothing can be searched against it")
    persist(baseline, "baseline", -1)
    model = model or CostModel(min_samples=max(6, len(space) + 2))
    X: List[List[float]] = [feats({})]
    y: List[float] = [sgn * baseline.value]
    best = baseline
    seen = {json.dumps(space.validate({}), sort_keys=True)}
    rejected: List[Dict] = []
    measured = 1
    model_proposed = 0
    model_hits = 0

    def propose(trial: int) -> tuple:
        explore = (not model.ready) or \
            (explore_every and trial % explore_every == 0)
        if explore:
            # trust region around the incumbent half the time once we
            # have one, pure random otherwise
            if best.config and rng.randint(2):
                return space.neighbor(best.config, rng), False
            return space.sample(rng), False
        cands, rows = [], []
        for _ in range(pool):
            c = space.neighbor(best.config, rng) if rng.randint(2) \
                else space.sample(rng)
            cands.append(c)
            rows.append(feats(c))
        for i in model.rank(rows):
            if json.dumps(cands[i], sort_keys=True) not in seen:
                return cands[i], True
        return cands[model.rank(rows)[0]], True

    for trial in range(int(budget)):
        cfg, from_model = propose(trial)
        fp = json.dumps(cfg, sort_keys=True)
        if fp in seen:
            continue
        seen.add(fp)
        res = measure_candidate(space, cfg, bench_fn, objective)
        if from_model:
            model_proposed += 1
        if not res.ok:
            rejected.append({"config": res.config,
                             "reject": res.reject})
            if log:
                _log.info("mxtune: trial %d rejected (%s) at %s",
                          trial, res.reject, res.config)
            continue
        measured += 1
        X.append(feats(cfg))
        y.append(sgn * res.value)
        persist(res, "search-trial", trial)
        if sgn * res.value < sgn * best.value:
            best = res
            if log:
                _log.info("mxtune: trial %d new best %s=%.6g at %s",
                          trial, objective, res.value, cfg)
        if from_model and sgn * res.value < sgn * baseline.value:
            model_hits += 1
        model.fit(X, y)

    speedup = (baseline.value / best.value if direction == "min"
               else best.value / baseline.value) \
        if best.value else None
    return {
        "objective": objective, "direction": direction,
        "baseline_value": baseline.value,
        "best_value": best.value, "best_config": best.config,
        "speedup": speedup, "budget": int(budget),
        "measured": measured, "rejected": rejected,
        "n_rejected": len(rejected),
        "model": model.describe(),
        "model_proposed": model_proposed, "model_hits": model_hits,
        "model_hit_rate": (model_hits / model_proposed
                           if model_proposed else None),
        "space_fingerprint": space.fingerprint(),
    }
