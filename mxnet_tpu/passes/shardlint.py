"""shardlint: structural verification of the sharded train step.

GSPMD failure modes are silent: drop an ``out_shardings`` annotation
and the step still trains — just with every buffer replicated (the
memory win gone) or with a surprise all-gather per step (the scaling
win gone). This pass turns the island ``parallel/hlo_check.py`` into a
first-class lint over :meth:`ShardedStepFunction.shard_report`:

- **plan-vs-compiled**: every parameter/optimizer-state output
  sharding of the compiled program must be equivalent to what the
  :class:`~mxnet_tpu.shard.ShardPlan` promised — an error means the
  annotation was dropped somewhere between the plan and XLA
  (accidental full replication is exactly this finding);
- **zero-applied**: with ZeRO on and a data-parallel axis >1, at least
  one optimizer-state buffer must actually be sharded;
- **gradient-exchange**: a data-parallel mesh must show a cross-replica
  reduction (all-reduce / reduce-scatter spanning the batch axis) in
  the compiled HLO — its absence means the batch isn't really sharded;
- **collective attribution**: every collective's replica groups are
  re-derived against the mesh (hlo_check); unparseable groups warn,
  groups matching no axis subset report at info (DPxTP resharding
  legitimately emits partial-axis permutes).

Exposed as ``shardlint`` in the default PassManager and as
``tools/mxlint.py --shard`` (a self-check over a tiny sharded step on
the local devices).
"""
from __future__ import annotations

from typing import Dict, List

from . import Finding, Pass

__all__ = ["ShardLint", "lint_shard_report"]


def _leaf_list(tree):
    import jax
    return jax.tree.flatten(tree)[0]


def lint_shard_report(report: Dict[str, object]) -> List[Finding]:
    """Findings for one ``ShardedStepFunction.shard_report()`` dict."""
    import jax
    from ..parallel.hlo_check import collective_report, summarize
    p = ShardLint()
    findings: List[Finding] = []
    plan = report["plan"]
    mesh = report["mesh"]
    n_batch = plan.axes[plan.batch_axis]

    # -- plan vs compiled shardings (params, then optimizer state) ------
    out_shardings = report["output_shardings"]
    for kind, want_tree, got_tree, ndim_tree in (
            ("param", report["pspec"], out_shardings[0],
             report["pndim"]),
            ("opt-state", report["sspec"], out_shardings[1],
             report["sndim"])):
        wants = _leaf_list(want_tree)
        gots = _leaf_list(got_tree)
        ndims = _leaf_list(ndim_tree)
        if len(wants) != len(gots):
            findings.append(p.finding(
                "sharding-structure", kind, "error",
                f"compiled {kind} shardings have {len(gots)} leaves, "
                f"plan has {len(wants)} — the annotation tree was not "
                "threaded through jit"))
            continue
        for i, (want, got, nd) in enumerate(zip(wants, gots, ndims)):
            try:
                ok = got.is_equivalent_to(want, nd)
            except Exception:
                ok = repr(got) == repr(want)
            if not ok:
                sev = "error"
                msg = (f"compiled {kind} sharding [{i}] is {got} but "
                       f"the plan says {want}")
                if getattr(got, "is_fully_replicated", False) and \
                        not getattr(want, "is_fully_replicated", True):
                    msg += " — accidental full replication"
                findings.append(p.finding(
                    "sharding-mismatch", f"{kind}[{i}]", sev, msg))

    # -- the batch really is sharded ------------------------------------
    # THE data-parallel annotation: every data input's COMPILED
    # sharding must span the batch axis. This is checked on the
    # compiled program, not the plan, because it is exactly the
    # annotation that can silently go missing (a dropped in_shardings
    # entry still trains — every replica just redundantly computes the
    # full global batch; batch-axis collective counts can't catch it
    # since the ZeRO update emits batch-axis all-reduces regardless).
    if n_batch > 1:
        input_shardings = report.get("data_shardings")
        if input_shardings is not None:
            for i, got in enumerate(_leaf_list(input_shardings)):
                if getattr(got, "is_fully_replicated", False):
                    findings.append(p.finding(
                        "data-input-replicated", f"input[{i}]",
                        "error",
                        f"data input [{i}] compiled FULLY REPLICATED "
                        f"on a {n_batch}-way '{plan.batch_axis}' "
                        "axis: every replica computes the whole "
                        "global batch — zero data-parallel compute "
                        "scaling; the in_shardings entry for the "
                        "inputs was dropped"))

    # -- ZeRO actually applied ------------------------------------------
    state_gots = _leaf_list(out_shardings[1])
    if plan.zero and n_batch > 1 and state_gots:
        if not any(not getattr(s, "is_fully_replicated", True)
                   for s in state_gots):
            findings.append(p.finding(
                "zero-not-applied", "opt-state", "error",
                f"plan has zero=True over a {n_batch}-way "
                f"'{plan.batch_axis}' axis but every optimizer-state "
                "buffer compiled fully replicated — per-replica "
                "optimizer memory will not scale 1/N"))

    # -- collectives ----------------------------------------------------
    infos = collective_report(report["hlo"], mesh)
    counts = summarize(infos)
    findings.append(p.finding(
        "collectives", "step", "info",
        "compiled collectives: " + (", ".join(
            f"{k} x{v}" for k, v in sorted(counts.items())) or "none")))
    for ci in infos:
        if ci.groups is None:
            findings.append(p.finding(
                "unparsed-collective", ci.op, "warn",
                f"replica_groups syntax not recognized: "
                f"{ci.line[:160]}"))
        elif ci.axes is None:
            findings.append(p.finding(
                "unattributed-collective", ci.op, "info",
                f"{ci.op} groups match no mesh-axis subset (partial-"
                f"axis resharding is normal under DPxTP): "
                f"{ci.line[:120]}"))
    if n_batch > 1:
        has_grad_reduce = any(
            ci.op in ("all-reduce", "reduce-scatter")
            and ci.axes and plan.batch_axis in ci.axes
            for ci in infos)
        if not has_grad_reduce:
            findings.append(p.finding(
                "no-gradient-exchange", "step", "warn",
                f"no all-reduce/reduce-scatter spans the "
                f"'{plan.batch_axis}' axis — the batch is probably "
                "not actually sharded (gradients need no cross-"
                "replica reduction only when every replica sees the "
                "whole batch)"))
    return findings


class ShardLint(Pass):
    """Verify a compiled sharded step's HLO/sharding annotations
    against its ShardPlan. Target: a ``shard_report()`` dict (or a
    :class:`ShardedStepFunction` plus cached report); ``run(None)``
    is a no-op — there is no global registry to audit."""

    name = "shardlint"

    def run(self, target=None) -> List[Finding]:
        if target is None:
            return []
        if isinstance(target, dict):
            return lint_shard_report(target)
        raise TypeError(
            "shardlint target must be a ShardedStepFunction."
            "shard_report() dict")
