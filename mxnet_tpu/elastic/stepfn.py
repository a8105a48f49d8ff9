"""ElasticStepFunction: the fused train step that survives membership
changes.

The one-program :class:`~mxnet_tpu.step.stepfn.StepFunction` compiles
the gradient exchange *into* the jit (identity or in-mesh psum) — a
shape that cannot abort mid-collective when a peer dies. The elastic
variant splits the step at exactly the exchange boundary:

- **grad program** — forward + backward, compiled once per input
  signature. Its trace is *world-size independent*: membership changes
  never touch it.
- **host exchange** — the flat-bucket allreduce through the elastic
  kvstore, generation-fenced: a :class:`MembershipChanged` aborts the
  step's exchange, the session rebuilds (barrier + bucket relayout +
  batch/LR rescale), and the SAME gradients are re-exchanged under the
  new generation — forward/backward is never recomputed for a bump.
- **update program** — the fused multi-tensor optimizer over the
  reduced gradients, donated buffers. The ``1/world`` normalization of
  the summed exchange rides ``rescale_grad``, a *structural* scalar of
  ``Optimizer.fused_signature()`` — so a world-size change re-keys
  **exactly this one program** (the acceptance budget: one re-key per
  generation bump, zero steady-state recompiles after the rebuild; a
  rejoin back to a previously-seen world size is a cache HIT and
  re-keys nothing).

The trainer keeps owning optimizer state (checkpoints, TrainGuard and
``save_states`` see post-update values), and the step boundary is also
the membership boundary: heartbeats go out here, generation bumps are
observed here, and the group leader publishes join state here.

With ``MXGUARD=1`` the split point gains the integrity vote
(mxnet_tpu/guard/): the grad program emits fingerprint taps, workers
exchange them through a generation-fenced round BEFORE the bucket
allreduce, and a corrupt replica is classified by deterministic
re-execution — transient faults retry in place, persistent ones
quarantine through the same leave/membership-bump machinery
(docs/resilience.md, integrity section).
"""
from __future__ import annotations

import time
from typing import Dict

import jax

from ..base import MXNetError
from ..ndarray.ndarray import _wrap
from ..obs import propagate as _obs_prop
from ..step.stepfn import StepFunction, _raw
from .. import trace as _trace
from .membership import MembershipChanged

__all__ = ["ElasticStepFunction"]


class ElasticStepFunction(StepFunction):
    _ties_shared = False  # buckets and votes go by name

    def __init__(self, net, loss_fn=None, trainer=None, **kwargs):
        if kwargs.get("psum_axis") is not None:
            raise MXNetError(
                "ElasticStepFunction owns the gradient exchange; "
                "psum_axis= does not compose with it")
        if trainer is None or getattr(trainer, "_elastic", None) is None:
            raise MXNetError(
                "ElasticStepFunction needs a trainer with an elastic "
                "session (create the Trainer with an ElasticKVStore, "
                "or call session.attach(trainer) first)")
        super().__init__(net, loss_fn, trainer=trainer, **kwargs)
        self._session = trainer._elastic
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        kv = trainer._kvstore
        if kv is None or not getattr(kv, "supports_flat_allreduce",
                                     False):
            raise MXNetError(
                "ElasticStepFunction needs a flat-allreduce-capable "
                f"kvstore; got {type(kv).__name__}")
        self._kv = kv
        self._grad_cache: Dict = {}
        self._buckets = None  # (GradientBuckets, layout signature)
        self._nstep = 0

    # ------------------------------------------------------------------
    # program caches
    # ------------------------------------------------------------------
    def _grad_key(self, inputs, guard=False):
        return (tuple((tuple(v.shape), str(v.dtype)) for v in inputs),
                self._param_dtypes(), self._opt_level, bool(guard)) \
            + self._shard_key()

    def _update_key(self):
        # rescale_grad (inside fused_signature) carries 1/world — THE
        # re-key on a world-size change; generation itself is absent,
        # so returning to a previously-seen world size is a cache hit
        return (self._param_dtypes(), self._opt_level,
                self._optimizer.fused_signature()) + self._shard_key()

    def _grad_fn(self, inputs, guard=False):
        key = self._grad_key(inputs, guard)
        fn = self._grad_cache.get(key)
        if fn is None:
            self._record_miss(inputs)
            # params NOT donated: the update program still needs the
            # pre-step weights — which is also what makes the mxguard
            # deterministic re-execution safe (guard/voting.py)
            fn = jax.jit(self._build_grads(taps=guard))
            self._grad_cache[key] = fn
        return fn

    def _update_fn(self):
        key = self._update_key()
        fn = self._cache.get(key)
        if fn is None:
            from ..telemetry import metrics as _metrics
            from ..telemetry import recompile as _recompile
            _metrics.counter(
                "fused_step_cache_misses_total",
                "fused-step signature-cache misses (compiles)").inc()
            sig = {"inputs": [], "world": int(self._session.world),
                   "rescale": float(self._optimizer.rescale_grad),
                   "phase": "update"}
            _recompile.record_recompile(
                f"ElasticStepFunction:{self._name}", sig,
                kind="fused_step")

            def pure_update(tvals, svals, grads, hyper):
                # the one-program step's own update segment (its
                # barrier pins the exchange/update boundary for the
                # same bitwise-contraction reason); the exchange
                # already happened on the host (_exchange is identity)
                return self._apply(tvals, grads, svals, hyper)

            fn = jax.jit(pure_update,
                         donate_argnums=(0, 1) if self._donate else ())
            self._cache[key] = fn
            self._last = (fn, key)
        return fn

    # ------------------------------------------------------------------
    # the host-side bucketed exchange
    # ------------------------------------------------------------------
    def _grad_buckets(self):
        """Bucket layout for the CURRENT world (rebuilt on a bump:
        the session's generation is part of the signature through
        world_size — step/buckets.GradientBuckets)."""
        from ..step.buckets import GradientBuckets
        items = []
        for i, n in zip(self._indices, self._trainable):
            p = self._param_objs[n]
            v = p.data() if hasattr(p, "data") else p
            items.append((i, tuple(v.shape), str(v.dtype),
                          v.size * v.dtype.itemsize))
        sig = (tuple(items), self._session.world)
        if self._buckets is None or self._buckets[1] != sig:
            self._buckets = (GradientBuckets(
                items, world_size=self._session.world), sig)
        return self._buckets[0]

    def _exchange_once(self, grads_by_name):
        """One attempt: flatten → fenced allreduce per bucket →
        scatter. Raises MembershipChanged whole (no partial effect:
        reduced segments only replace the local grads after EVERY
        bucket of the generation succeeded)."""
        name_of = dict(zip(self._indices, self._trainable))
        grads_by_idx = {i: grads_by_name[name_of[i]]
                        for i in self._indices}
        buckets = self._grad_buckets()
        reduced_parts = []
        for bid, bucket in enumerate(buckets.buckets):
            flat = buckets.flatten(bucket, grads_by_idx)
            out = self._kv.allreduce_flat(f"__estep_b{bid}",
                                          _wrap(flat))
            reduced_parts.append((bucket, out._data))
        reduced = {}
        for bucket, flat in reduced_parts:
            for i, seg in buckets.unflatten(bucket, flat).items():
                reduced[name_of[i]] = seg
        return reduced

    def _exchange(self, grads):
        """In-jit hook disabled: the elastic exchange is host-side."""
        return grads

    def _set_rescale(self, batch_size):
        # summed exchange + 1/(local batch x world) = the global-batch
        # mean — the update math of an uninterrupted run at this world
        self._optimizer.rescale_grad = \
            self._scale / (batch_size * max(1, self._session.world))

    # ------------------------------------------------------------------
    # mxguard: the pre-averaging fingerprint vote (guard/voting.py)
    # ------------------------------------------------------------------
    def _guard_grads(self, grads_fn, pvals, inputs, rng):
        """One gradient computation with the taps: run the grad
        program, evaluate the sdc drill sites (the injection models
        the hardware — it fires per attempt, so re-executions see a
        persistent fault again and a one-shot ``@K`` clause clears),
        and return (grads, extras, loss, host fingerprint matrix) with
        any corrupted row recomputed host-side so the reported
        fingerprint describes the bytes this worker contributes."""
        import numpy as onp
        from ..guard.voting import apply_sdc, sdc_token
        grads, extras, loss, fps = grads_fn(pvals, inputs, rng)
        fps_host = onp.asarray(fps, dtype=onp.float32)
        token = sdc_token(self._session.worker_id, self._nstep,
                          self._session.world)
        if token is not None:
            from .. import config
            grads, name, row = apply_sdc(
                grads, self._trainable, token, self._nstep,
                seed=int(config.get("MXRESIL_SEED")))
            fps_host = fps_host.copy()
            fps_host[1 + self._trainable.index(name)] = row
        return grads, extras, loss, fps_host

    def _guard_vote(self, grads_fn, pvals, inputs, rng, grads,
                    fps_host):
        """Rounds A/B of the pre-exchange fingerprint vote (module
        docstring of guard/voting.py). Returns possibly-replaced
        (grads, fps) on a transient verdict; raises
        :class:`GuardQuarantined` / :class:`GuardCorruption` on a
        persistent one; a :class:`MembershipChanged` fence propagates
        to the caller's rebuild loop like any other fenced round."""
        import numpy as onp
        from .. import config
        from ..guard.fingerprint import vote
        from ..guard.voting import (GuardCorruption, GuardQuarantined,
                                    contribution, table_of)
        from ..telemetry import metrics as _metrics
        session = self._session
        me = session.worker_id
        step = self._nstep
        n_grads = len(self._trainable)

        if session.world <= 1:
            # solo: no peers to vote with — self-check on non-finite
            # GRADIENT fingerprints (a non-finite loss is divergence
            # territory — TrainGuard's rollback, not quarantine),
            # classify by re-execution
            if float(fps_host[1:1 + n_grads, 2].sum()) <= 0:
                return grads, fps_host
            _metrics.counter(
                "mxguard_suspect_verdicts_total",
                "fingerprint verdicts naming a suspect replica").inc()
            grads2, _, _, fps2 = self._guard_grads(
                grads_fn, pvals, inputs, rng)
            if onp.array_equal(fps_host, fps2, equal_nan=True):
                self.guard_events.append(
                    {"step": step, "kind": "persistent",
                     "suspect": me, "reasons": ["nonfinite"]})
                _metrics.counter(
                    "mxguard_hard_fails_total",
                    "solo runs hard-failed on persistent "
                    "corruption").inc()
                raise GuardCorruption(step, ["nonfinite"])
            self.guard_events.append(
                {"step": step, "kind": "transient", "suspect": me,
                 "reasons": ["nonfinite"]})
            _metrics.counter(
                "mxguard_transient_total",
                "transient corruption healed by re-execution").inc()
            return grads2, fps2

        workers = session.view.workers
        rank = session.rank
        world = session.world
        tol = float(config.get("MXGUARD_VOTE_TOL"))
        # the exchanged table carries params digest + gradient rows;
        # the trailing LOCAL loss row stays home (losses legitimately
        # differ per worker — they would only add vote noise)
        voted = fps_host[:1 + n_grads]
        table = table_of(session.allreduce(
            "__guard_fp", contribution(voted, rank, world)), world)
        _metrics.counter(
            "mxguard_votes_total",
            "cross-replica fingerprint vote rounds").inc()
        verdict = vote(table, workers, tol=tol)
        if verdict.clean:
            return grads, fps_host
        if verdict.global_anomaly:
            # every replica agrees the gradients are bad: divergence,
            # not silent corruption — TrainGuard's jurisdiction
            self.guard_events.append(
                {"step": step, "kind": "global-anomaly",
                 "suspect": None, "reasons": ["all-replicas"]})
            return grads, fps_host
        _metrics.counter("mxguard_suspect_verdicts_total",
                         "fingerprint verdicts naming a suspect "
                         "replica").inc()
        suspects = verdict.suspects
        _log_reasons = sorted(
            {r for rs in suspects.values() for r in rs})
        self.guard_events.append(
            {"step": step, "kind": "suspect",
             "suspect": sorted(suspects),
             "reasons": _log_reasons})
        # round B: suspects re-execute on the same inputs, everyone
        # re-contributes — the SAME deterministic verdict again tells
        # every worker how the step ends
        if me in suspects:
            with _trace.span("guard.reexec", "guard", step=step,
                             suspect=me):
                grads, _, _, fps_host = self._guard_grads(
                    grads_fn, pvals, inputs, rng)
        table2 = table_of(session.allreduce(
            "__guard_fp2",
            contribution(fps_host[:1 + n_grads], rank, world)), world)
        verdict2 = vote(table2, workers, tol=tol)
        if me in verdict2.suspects:
            # reproduced under re-execution: persistent. Quarantine —
            # leave (the membership bump survivors fence on) and raise
            _metrics.counter(
                "mxguard_quarantines_total",
                "replicas quarantined for persistent corruption").inc()
            self.guard_events.append(
                {"step": step, "kind": "persistent", "suspect": me,
                 "reasons": verdict2.suspects[me]})
            # coordinated capture BEFORE leaving: the post-mortem needs
            # every live rank's recorder, not just the quarantined one
            if hasattr(session, "request_pod_dump"):
                session.request_pod_dump(f"guard-quarantine-{me}")
            session.leave()
            raise GuardQuarantined(me, step, verdict2.suspects[me])
        if me in suspects:
            _metrics.counter(
                "mxguard_transient_total",
                "transient corruption healed by re-execution").inc()
            self.guard_events.append(
                {"step": step, "kind": "transient", "suspect": me,
                 "reasons": suspects[me]})
        return grads, fps_host

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------
    def step(self, x, *labels, batch_size=None, rng_raw=None):
        from ..telemetry import metrics as _metrics
        from .. import telemetry as _telemetry
        t0 = time.perf_counter()
        session = self._session
        # derived pod identity (mxobs): every rank computes the SAME
        # pod.step trace id from (group uid, generation, step) captured
        # at entry — lockstep ranks agree, so the per-rank step trees
        # stitch into one trace under `mxprof trace --dir`. None when
        # MXOBS/MXTRACE is off or the session has no pod uid yet.
        gen0, step0 = session.generation, self._nstep
        pod_ctx = _obs_prop.pod_step_context(
            getattr(session, "pod_uid", None), gen0, step0)
        t_root0 = time.perf_counter_ns()
        # the per-step trace root, keyed by (generation, step) — the
        # cross-subsystem correlation key: heartbeat/rebuild, grad
        # dispatch, guard vote, bucket exchange and update all
        # decompose as children of this one span
        with _trace.under(pod_ctx), \
             _trace.span("train.step", "train", step=self._nstep,
                         generation=session.generation,
                         world=session.world, fn=self._name,
                         kind=type(self).__name__) as _st:
            # the step boundary IS the membership boundary
            with _trace.span("elastic.heartbeat", "elastic",
                             step=self._nstep) as _hb:
                changed = session.heartbeat(self._nstep)
                _hb.set(generation_changed=changed)
            if changed:
                session.rebuild()
                _st.set(generation=session.generation,
                        world=session.world)
            inputs = tuple(_raw(a) for a in (x,) + labels)
            self._prepare(inputs)
            if batch_size is None:
                batch_size = int(inputs[0].shape[0]) \
                    if inputs[0].ndim else 1
            self._set_rescale(batch_size)
            guard = self._guard_enabled()

            with _trace.span("step.prep", "train"):
                grads_fn = self._grad_fn(inputs, guard)
                hyper = self._hyper()
                pvals, svals = self._gather()
                from .. import random as _random
                import jax.numpy as jnp
                rng = jnp.asarray(rng_raw) if rng_raw is not None \
                    else jax.random.key_data(_random.next_key())
            fps_host = None
            with _trace.span("step.grads", "train", guard=guard,
                             batch=batch_size):
                if guard:
                    grads, extras, loss, fps_host = self._guard_grads(
                        grads_fn, pvals, inputs, rng)
                else:
                    grads, extras, loss = grads_fn(pvals, inputs, rng)

            t1 = time.perf_counter()
            while True:
                try:
                    if guard:
                        # the pre-averaging vote: a corrupt replica is
                        # caught BEFORE its gradients enter the
                        # allreduce
                        with _trace.span("guard.vote", "guard",
                                         step=self._nstep,
                                         world=session.world):
                            grads, fps_host = self._guard_vote(
                                grads_fn, pvals, inputs, rng, grads,
                                fps_host)
                    with _trace.span(
                            "step.exchange", "elastic",
                            generation=session.generation,
                            world=session.world) as _ex:
                        reduced = self._exchange_once(grads)
                        # bucket count from the layout _exchange_once
                        # just memoized — rebuilding the O(n_params)
                        # signature for a span attribute would tax
                        # every step, traced or not
                        if self._buckets is not None:
                            _ex.set(buckets=len(
                                self._buckets[0].buckets))
                    break
                except MembershipChanged:
                    # fenced mid-exchange: rebuild with the survivors
                    # and re-exchange the SAME gradients under the new
                    # generation — forward/backward is not recomputed
                    session.rebuild()
                    self._set_rescale(batch_size)
                    _st.set(generation=session.generation,
                            world=session.world, rebuilt=True)
            t2 = time.perf_counter()

            with _trace.span("step.update", "train"):
                update_fn = self._update_fn()
                tvals = {n: pvals[n] for n in self._trainable}
                new_w, new_s = update_fn(tvals, svals, reduced, hyper)
                new_params = dict(zip(self._trainable, new_w))
                new_params.update(extras)
                self._writeback(new_params, new_s)
            if guard:
                flagged = any(e["step"] == self._nstep
                              for e in self.guard_events)
                self._guard_note(fps_host, loss, inputs, rng,
                                 good=not flagged, strict=False)
            t3 = time.perf_counter()

        if pod_ctx is not None and session.is_leader:
            # exactly one rank records the shared pod.step root the
            # other ranks' step trees already parent under (leadership
            # read AFTER the step: a mid-step rebuild may have moved it)
            _obs_prop.emit_pod_root(
                session.pod_uid, gen0, step0, t_root0,
                time.perf_counter_ns(), world=session.world)
        self._nstep += 1
        session.note_step(batch_size)
        _metrics.histogram(
            "mxelastic_exchange_seconds",
            "elastic bucketed gradient-exchange latency (including "
            "any rebuild absorbed mid-step)").observe(t2 - t1)
        _metrics.histogram(
            "fused_step_dispatch_seconds",
            "fused-step compiled-call dispatch (async; excludes "
            "device wait)").observe((t1 - t0) + (t3 - t2))
        _telemetry.record_step(batch_size, time.perf_counter() - t0)
        return _wrap(loss)

    __call__ = step

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def guard_state(self) -> Dict[str, object]:
        state = super().guard_state()
        state["exchanges_gradients"] = True
        state["kvstore"] = type(self._kv).__name__
        state["world"] = int(self._session.world)
        return state

    def program_counts(self) -> Dict[str, int]:
        """Per-instance compiled-program census — the drill's re-key
        budget check reads this (grad programs never re-key on a
        membership change; update programs re-key once per NEW world
        size)."""
        return {"grad": len(self._grad_cache),
                "update": len(self._cache),
                "total": len(self._grad_cache) + len(self._cache)}
