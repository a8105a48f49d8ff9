"""mxopt: the optimizing graph compiler over the Symbol IR.

PR 1 built the pass layer as *diagnosis* (``mxnet_tpu/passes/`` — every
pass reads the graph and emits Findings). This package is the transform
half the reference got from NNVM and TVM/Relay get from their transform
pipelines: rewrite passes that return a NEW graph, run at bind time
behind the ``MXNET_GRAPH_OPT`` level:

- **0** (default): off — the graph compiles exactly as written;
- **1**: semantics-preserving cleanups (constant folding, CSE,
  identity/no-op elision, dead-node sweep) — bitwise parity class;
- **2**: level 1 plus fusion-group partitioning (conv+bn+relu,
  matmul+activation, elementwise chains, attention — per "Operator
  Fusion in XLA", the patterns worth making explicit) and TPU layout
  selection (NHWC convolution regions with the minimal boundary
  transpose set) — tolerance-tagged parity (contraction order moves).

Entry points: :func:`optimize_symbol` (used by ``Executor`` bind,
symbol-mode ``StepFunction`` — which composes with shard plans: same
in/out shardings over the optimized graph — and serve AOT warmup
via the executor path), :func:`opt_level`, :func:`build_manager`.
Every pass rides the PassManager registry with an explicit ``order``
key, emits Findings ``tools/mxlint.py --opt`` can render, and bumps
per-pass rewrite counters + time-in-pass histograms in the telemetry
registry (``tools/mxprof.py opt`` renders the report).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from ..base import get_env
from ..passes import Finding, PassManager, findings_report  # noqa: F401
from ..symbol.symbol import Symbol
from .rewrite import MutableGraph, RewritePass
from .verify import (TOLERANCE_CLASSES, parity_check, random_value_map,
                     strongest_class, tolerance_for)

__all__ = ["optimize_symbol", "opt_level", "build_manager", "OptReport",
           "MutableGraph", "RewritePass", "parity_check",
           "random_value_map", "TOLERANCE_CLASSES", "tolerance_for"]


def opt_level(explicit: Optional[int] = None) -> int:
    """Resolve the active optimization level (explicit arg wins, else
    the MXNET_GRAPH_OPT flag), clamped to the shipped range."""
    lvl = explicit if explicit is not None \
        else get_env("MXNET_GRAPH_OPT", 0)
    try:
        lvl = int(lvl)
    except (TypeError, ValueError):
        lvl = 0
    return max(0, min(2, lvl))


def build_manager(level: int) -> PassManager:
    """The rewrite pipeline for ``level``, assembled fresh on a
    PassManager (execution order = the explicit ``order`` keys:
    fold(10) → cse(20) → elide(30) → layout(40) → fuse(50) →
    dce(90))."""
    from .passes_basic import (CommonSubexpr, ConstantFold,
                               DeadNodeSweep, IdentityElide)
    from .fuse import FusionPartition
    from .layout import LayoutSelect
    pm = PassManager()
    for p in (ConstantFold(), CommonSubexpr(), IdentityElide(),
              LayoutSelect(), FusionPartition(), DeadNodeSweep()):
        if p.min_level <= level:
            pm.register(p)
    return pm


class OptReport:
    """What the pipeline did to one graph: per-pass rewrite counts and
    timings, the fused-pattern census, the aggregate tolerance class,
    and every Finding the passes emitted (mxlint-schema)."""

    def __init__(self, level: int, where: str):
        self.level = level
        self.where = where
        self.passes: List[Dict[str, object]] = []
        self.findings: List[Finding] = []
        self.fused_census: Dict[str, int] = {}
        self.nodes_before = 0
        self.nodes_after = 0
        self.reverted = None  # failure reason when the graph reverted
        self.verified = None  # True/False/None(=not run)

    def add_pass(self, name: str, rewrites: int, seconds: float,
                 findings: List[Finding]):
        self.passes.append({"pass": name, "rewrites": rewrites,
                            "seconds": round(seconds, 6)})
        self.findings.extend(findings)

    @property
    def total_rewrites(self) -> int:
        return sum(p["rewrites"] for p in self.passes)

    @property
    def tolerance_class(self) -> str:
        fired = [p for p in self.passes if p["rewrites"]]
        classes = ["bitwise"] + [
            getattr(_PASS_CLASSES.get(p["pass"]), "tolerance_class",
                    "bitwise") for p in fired]
        return strongest_class(classes)

    def to_dict(self) -> Dict[str, object]:
        return {
            "level": self.level, "where": self.where,
            "passes": list(self.passes),
            "total_rewrites": self.total_rewrites,
            "tolerance_class": self.tolerance_class,
            "fused_census": dict(self.fused_census),
            "nodes_before": self.nodes_before,
            "nodes_after": self.nodes_after,
            "reverted": self.reverted,
            "verified": self.verified,
            "findings": [f.to_dict() for f in self.findings],
        }


# pass-name -> pass class (tolerance-class lookup for reports)
_PASS_CLASSES: Dict[str, type] = {}


def _register_classes():
    from . import passes_basic, fuse, layout
    for mod in (passes_basic, fuse, layout):
        for obj in vars(mod).values():
            if isinstance(obj, type) and issubclass(obj, RewritePass) \
                    and obj is not RewritePass:
                _PASS_CLASSES[obj.name] = obj


def _metric_suffix(pass_name: str) -> str:
    return pass_name.split(".")[-1]


def _io_contract_violation(orig: Symbol, opt: Symbol) -> Optional[str]:
    """The optimizer must not change the graph's binding surface."""
    if orig.list_arguments() != opt.list_arguments():
        return (f"argument list changed: {orig.list_arguments()} -> "
                f"{opt.list_arguments()}")
    if orig.list_auxiliary_states() != opt.list_auxiliary_states():
        return (f"aux list changed: {orig.list_auxiliary_states()} -> "
                f"{opt.list_auxiliary_states()}")
    if len(orig._outputs) != len(opt._outputs):
        return (f"output arity changed: {len(orig._outputs)} -> "
                f"{len(opt._outputs)}")
    return None


def optimize_symbol(symbol: Symbol, level: Optional[int] = None,
                    where: str = "",
                    value_map: Optional[dict] = None
                    ) -> Tuple[Symbol, Optional[OptReport]]:
    """Run the rewrite pipeline on ``symbol`` at ``level``.

    Returns ``(optimized_symbol, report)`` — the input Symbol is never
    mutated. At level 0 (or if every safety gate trips) the original
    comes back unchanged. When ``MXNET_GRAPH_OPT_VERIFY`` is set and
    ``value_map`` is provided (Executor hands in its live buffers), the
    optimized graph is parity-checked against the original under the
    report's tolerance class before being accepted; a failure REVERTS
    to the unoptimized graph — optimization is never allowed to change
    results past its declared class.
    """
    from ..telemetry import metrics as _metrics
    lvl = opt_level(level)
    if lvl <= 0:
        return symbol, None
    if not _PASS_CLASSES:
        _register_classes()
    report = OptReport(lvl, where)
    _metrics.counter("graph_opt_graphs_total",
                     "graphs run through the optimizing pipeline").inc()
    graph = MutableGraph(symbol)
    report.nodes_before = graph.node_count()
    pm = build_manager(lvl)
    for name in pm.ordered_names():
        p = pm.get(name)
        t0 = time.perf_counter()
        try:
            n, findings = p.apply(graph)
        except Exception as e:  # a broken pass must not break bind
            report.reverted = (f"pass {name} raised "
                               f"{type(e).__name__}: {e}")
            report.findings.append(Finding(
                name, "pass-error", where or "<graph>", "error",
                report.reverted))
            _metrics.counter(
                "graph_opt_reverts_total",
                "graphs reverted to unoptimized (contract/verify/pass "
                "failure)").inc()
            return symbol, report
        dt = time.perf_counter() - t0
        report.add_pass(name, n, dt, findings)
        sfx = _metric_suffix(name)
        _metrics.counter(
            f"graph_opt_{sfx}_rewrites_total",
            f"rewrites applied by the {name} pass").inc(n)
        _metrics.histogram(
            f"graph_opt_{sfx}_seconds",
            f"time in the {name} pass per graph").observe(dt)
        census = getattr(p, "last_census", None)
        if census:
            for pattern, cnt in census.items():
                report.fused_census[pattern] = \
                    report.fused_census.get(pattern, 0) + cnt
                _metrics.counter(
                    f"graph_opt_fused_{pattern}_total",
                    f"fused groups formed for pattern {pattern}"
                    ).inc(cnt)
    _metrics.counter("graph_opt_rewrites_total",
                     "total graph rewrites applied"
                     ).inc(report.total_rewrites)
    optimized = graph.to_symbol()
    report.nodes_after = graph.node_count()

    if report.total_rewrites == 0:
        return symbol, report  # nothing fired: keep the original object

    bad = _io_contract_violation(symbol, optimized)
    if bad is not None:
        report.reverted = bad
        report.findings.append(Finding(
            "opt.pipeline", "io-contract", where or "<graph>", "error",
            f"optimized graph changed the binding surface ({bad}); "
            f"reverted to the unoptimized graph"))
        _metrics.counter("graph_opt_reverts_total",
                         "graphs reverted to unoptimized (contract/"
                         "verify/pass failure)").inc()
        return symbol, report

    if value_map is not None and get_env("MXNET_GRAPH_OPT_VERIFY",
                                         False):
        # check BOTH modes: a rewrite bug confined to the train branch
        # (BN batch stats, fused-group aux write-back) must not slip
        # past a gate that only ran inference (the training arg adds
        # train mode on top, it never replaces the eval check)
        ok, problems = parity_check(symbol, optimized, value_map,
                                    training=False,
                                    tol_class=report.tolerance_class)
        if ok:
            ok, problems = parity_check(
                symbol, optimized, value_map, training=True,
                tol_class=report.tolerance_class)
        report.verified = ok
        if not ok:
            report.reverted = "; ".join(problems)[:500]
            report.findings.append(Finding(
                "opt.pipeline", "verify", where or "<graph>", "error",
                f"parity check failed, reverted: {report.reverted}"))
            _metrics.counter("graph_opt_verify_failures_total",
                            "bind-time parity checks that failed "
                            "(graph reverted)").inc()
            _metrics.counter("graph_opt_reverts_total",
                             "graphs reverted to unoptimized (contract/"
                             "verify/pass failure)").inc()
            return symbol, report
    return optimized, report
