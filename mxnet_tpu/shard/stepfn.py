"""ShardedStepFunction: the fused train step over a named device mesh.

PR 5's :class:`~mxnet_tpu.step.StepFunction` compiles forward +
backward + exchange + optimizer into one donated XLA program, but
models distribution as kvstore-style allreduce over fully replicated
buffers — per-replica memory and the weight-update computation do not
scale with device count. This subclass rebuilds the same program on
``jax.jit`` + ``NamedSharding`` (GSPMD; SNIPPETS.md [1]-[3]):

- **inputs** shard their batch dim over the plan's ``batch`` axis, so
  each replica traces/computes only its slice of the global batch and
  XLA inserts the cross-replica gradient all-reduce itself (the vjp of
  a sharded batch against replicated weights IS the exchange — no
  explicit psum, no kvstore data plane);
- **parameters** are replicated by default, or tensor-sharded where a
  ``param_specs`` pattern says so (``P("batch", "model")`` composition
  with zero user-model changes);
- **optimizer state** is ZeRO-sharded along the batch axis
  (``ShardPlan.state_spec``), which drags the whole weight-update
  computation into sharded form through SPMD propagation — per-replica
  optimizer memory is ~1/N and the update math runs 1/N-sized per
  replica, exactly the transformation of "Automatic Cross-Replica
  Sharding of Weight Update in Data-Parallel Training".

Everything else — signature cache, recompile auditing, donation,
write-back, bitwise-stable hyper scalars — is inherited; one compiled,
sharding-annotated program per signature with zero steady-state
recompiles. ``shard_report()`` exposes the compiled HLO + shardings
for the ``shardlint`` pass; install-time gauges feed
``tools/mxprof.py shard``. See docs/sharding.md.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

from ..base import MXNetError
from ..optimizer import _state_rebind, _state_values
from ..step.stepfn import StepFunction, _raw
from .plan import ShardPlan

__all__ = ["ShardedStepFunction"]


class ShardedStepFunction(StepFunction):
    """Drop-in :class:`StepFunction` running GSPMD-sharded over a
    :class:`~mxnet_tpu.shard.ShardPlan`'s mesh::

        plan = ShardPlan(axes={"batch": -1})
        fused = trainer.fuse_step(net, loss_fn, shard_plan=plan)
        loss = fused.step(x, y)        # global batch; one program

    The global batch must divide by the plan's batch-axis size.
    """

    _ties_shared = False  # the plan places parameters by name

    def __init__(self, net, loss_fn=None, shard_plan: ShardPlan = None,
                 **kwargs):
        if kwargs.get("psum_axis") is not None:
            raise MXNetError(
                "ShardedStepFunction lowers the gradient exchange via "
                "GSPMD sharding propagation; psum_axis is the "
                "shard_map/ParallelTrainer mechanism — don't pass both")
        self._plan = shard_plan if shard_plan is not None else ShardPlan()
        self._installed = False
        super().__init__(net, loss_fn, **kwargs)

    @property
    def plan(self) -> ShardPlan:
        return self._plan

    # ------------------------------------------------------------------
    # spec trees
    # ------------------------------------------------------------------
    def _param_sharding(self, name, value):
        if name not in self._trainable:
            # non-trainable params and aux (BN running stats) replicate
            return self._plan.replicated()
        return self._plan.param_spec(name, value)

    def _pspec_tree(self, pvals):
        out = {}
        for n, v in pvals.items():
            if n == "__aux__":  # symbol-mode aux sub-dict
                out[n] = {k: self._plan.replicated() for k in v}
            else:
                out[n] = self._param_sharding(n, v)
        return out

    def _sspec_tree(self, svals):
        out = []
        for name, sval in zip(self._trainable, svals):
            out.append(jax.tree.map(
                lambda v, _n=name: self._plan.state_spec(_n, v), sval))
        return out

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def install(self) -> Dict[str, object]:
        """Place parameters and optimizer state onto the mesh per the
        plan (rebinding the NDArrays in place, so trainers/checkpoints
        keep their references), update the ``shard_*`` telemetry
        gauges, and return the per-replica memory report. Runs once,
        lazily, before the first compile; call again after a restore
        to re-place restored host arrays."""
        plan = self._plan
        if self._symbol_mode:
            items = list(self._param_objs.items())
            for n, v in self._aux_objs.items():
                v._rebind(jax.device_put(v._data, plan.replicated()))
        else:
            if self._plist is None:
                raise MXNetError("install() before parameter "
                                 "resolution — call step() (or resolve "
                                 "shapes with one forward) first")
            items = [(n, p.data()) for n, p in self._plist]
        for n, arr in items:
            arr._rebind(jax.device_put(
                arr._data, self._param_sharding(n, arr._data)))
        upd = self._updater
        for i, name in zip(self._indices, self._trainable):
            sval = _state_values(upd.states[i])
            placed = jax.tree.map(
                lambda v, _n=name: jax.device_put(
                    v, plan.state_spec(_n, v)), sval)
            _state_rebind(upd.states[i], placed)
        self._installed = True
        return self._refresh_gauges()

    def _refresh_gauges(self):
        from ..telemetry import metrics as _metrics
        pvals, svals = self._gather()
        pvals = dict(pvals)
        pvals.pop("__aux__", None)
        report = self._plan.memory_report(pvals.values(), svals)
        _metrics.gauge("shard_mesh_devices",
                       "devices in the sharded-step mesh"
                       ).set(report["devices"])
        for kind in ("params", "opt_state"):
            _metrics.gauge(f"shard_{kind}_bytes_total",
                           f"global bytes of {kind} under the shard "
                           "plan").set(report[kind]["total_bytes"])
            _metrics.gauge(f"shard_{kind}_bytes_per_replica",
                           f"max per-device bytes of {kind} (the "
                           "ZeRO win is this shrinking 1/N)"
                           ).set(report[kind]["per_replica_bytes"])
        return report

    def memory_report(self) -> Dict[str, object]:
        """Current per-replica params/opt-state accounting (also
        refreshes the ``shard_*`` gauges)."""
        return self._refresh_gauges()

    # ------------------------------------------------------------------
    # compile hooks
    # ------------------------------------------------------------------
    def _shard_key(self):
        return (self._plan.fingerprint(),)

    def _miss_signature_extra(self):
        # the plan fingerprint rides the recompile record so a re-plan
        # on identical shapes classifies as ``key-change`` (the honest
        # re-key), not cache eviction — tools/mxprof.py step renders it
        return {"plan": self._plan.fingerprint()}

    def _make_jit(self, pure, guard=False):
        if not self._installed:
            self.install()
        plan = self._plan
        pvals, svals = self._gather()
        pspec = self._pspec_tree(pvals)
        sspec = self._sspec_tree(svals)
        rep = plan.replicated()
        # the per-step rates and weight decays: one replicated array.
        # data_spec as a pytree prefix: every input (x and labels)
        # shards its batch dim — THE data-parallel annotation; each
        # replica computes only its slice of the global batch
        in_shardings = (pspec, sspec, rep, plan.data_spec(), rep)
        # loss sharding unconstrained: per-sample losses stay sharded
        # by batch through propagation, scalar losses replicate. The
        # mxguard fingerprint output is REPLICATED: its gradient
        # reductions cross the batch axis, so the taps compose with
        # the sharded weight-update forms unchanged (every replica
        # reads the same digest of the same global gradients).
        out_shardings = (pspec, sspec, None) + \
            ((rep,) if guard else ())
        if plan.n_devices > 1:
            # GSPMD partitions this program: trace it where the model
            # code can see that (a Pallas kernel cannot be partitioned)
            from ..ops.pallas_kernels import gspmd_partitioned
            single = pure

            def pure(*args):
                with gspmd_partitioned():
                    return single(*args)
        return jax.jit(pure,
                       in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1) if self._donate else ())

    def step(self, x, *labels, batch_size=None):
        raw = [_raw(a) for a in (x,) + labels]
        n = self._plan.n_batch
        if raw[0].ndim and raw[0].shape[0] % n:
            raise MXNetError(
                f"sharded step: global batch {raw[0].shape[0]} does not "
                f"divide by the '{self._plan.batch_axis}' axis size "
                f"{n} (mesh {self._plan.axes})")
        # place the batch on the mesh here: jit refuses an argument
        # that is committed elsewhere (nd.array(..., ctx=mx.tpu(0)), the
        # reference idiom, commits to one chip) instead of moving it
        placed = [jax.device_put(v, self._plan.data_spec(v)) for v in raw]
        return super().step(*placed, batch_size=batch_size)

    __call__ = step

    # ------------------------------------------------------------------
    # mxguard: per-device shard digests (guard/fingerprint.py)
    # ------------------------------------------------------------------
    def guard_digest_report(self) -> Dict[str, object]:
        """Cross-device integrity sweep over the mesh-placed
        parameters and optimizer state: every pair of devices holding
        the SAME shard index of the same buffer must hold
        bitwise-identical bytes (replicated weights, and the ZeRO
        state's replicated dimensions). A deviating device is named
        directly — the sharded path's analog of the cross-replica
        fingerprint vote, where the redundancy lives across mesh
        devices instead of kvstore workers."""
        from ..guard.fingerprint import (check_replica_digests,
                                         replica_digests)
        pvals, svals = self._gather()
        pvals = dict(pvals)
        pvals.pop("__aux__", None)
        named = list(pvals.items())
        for name, sval in zip(self._trainable, svals):
            for j, leaf in enumerate(jax.tree.leaves(sval)):
                named.append((f"opt_state:{name}:{j}", leaf))
        mismatches = check_replica_digests(named)
        from ..telemetry import metrics as _metrics
        _metrics.counter(
            "mxguard_shard_digest_sweeps_total",
            "per-device shard-digest integrity sweeps").inc()
        if mismatches:
            _metrics.counter(
                "mxguard_shard_digest_mismatches_total",
                "devices whose shard bytes diverged from the majority"
                ).inc(len(mismatches))
        return {"buffers": len(named),
                "devices": self._plan.n_devices,
                "mismatches": mismatches,
                "digests": {name: replica_digests(arr)
                            for name, arr in named[:4]}}

    # ------------------------------------------------------------------
    # introspection (shardlint / docs)
    # ------------------------------------------------------------------
    def shard_report(self, x, *labels) -> Dict[str, object]:
        """Lower the current compiled step and return the structural
        evidence the ``shardlint`` pass verifies: post-SPMD HLO text,
        the compiled input/output shardings, the mesh and the plan.
        A persistent-cache hit when the step already ran."""
        compiled = self.compiled(x, *labels)
        pvals, svals = self._gather()
        # pure_step(pvals, svals, hyper, inputs, rng): the batch is the
        # fourth argument, and only here is that known
        return {"hlo": compiled.as_text(),
                "input_shardings": compiled.input_shardings,
                "data_shardings": compiled.input_shardings[0][3],
                "output_shardings": compiled.output_shardings,
                "mesh": self._plan.mesh,
                "plan": self._plan,
                "pspec": self._pspec_tree(pvals),
                "sspec": self._sspec_tree(svals),
                "pndim": jax.tree.map(lambda v: v.ndim, pvals),
                "sndim": [jax.tree.map(lambda v: v.ndim, s)
                          for s in svals]}
