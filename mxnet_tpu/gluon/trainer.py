"""Gluon Trainer.

ref: python/mxnet/gluon/trainer.py (495 LoC) — optimizer driver over
KVStore: _init_kvstore :169, step :305, allreduce_grads :334, update :366.
On TPU the gradient "allreduce" across local devices is a no-op (one buffer
per param; the multi-chip reduce is a psum inside a pjit'd step — see
parallel/), but the kvstore plumbing and update_on_kvstore semantics are
preserved so distributed workflows match the reference's.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from .. import optimizer as opt_mod
from ..base import MXNetError
from ..kvstore import KVStoreBase, create as kv_create
from ..model import _create_kvstore
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._param2idx[param.name] = i
            self._params.append(param)
            param._trainer = self
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_params = {"kvstore": kvstore,
                                "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = []
        self._contains_sparse_weight = False
        self._contains_sparse_grad = False
        self._grad_buckets = None  # lazy; see _allreduce_grads
        self._shard_plan = None  # set by fuse_step(shard_plan=...)
        self._elastic = None  # ElasticSession (elastic kvstore attach)

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an " \
                "Optimizer instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer,
                                             param_dict=param_dict,
                                             **optimizer_params)
        self._updaters = [opt_mod.get_updater(self._optimizer)]

    def _init_kvstore(self):
        """ref: trainer.py:169."""
        config = self._kvstore_params
        kvstore, update_on_kvstore = _create_kvstore(
            config["kvstore"], 1,
            {p.name: p.data() for p in self._params
             if p._data is not None})
        if config["update_on_kvstore"] is not None:
            update_on_kvstore = config["update_on_kvstore"]
        if kvstore is not None:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            if getattr(kvstore, "session", None) is None:
                # elastic stores hold no weights (the exchange is a
                # stateless fenced allreduce; weights live on the
                # workers), so there is nothing to init server-side —
                # and deferred-shape parameters stay deferred
                for i, param in enumerate(self._params):
                    if param.grad_req != "null":
                        kvstore.init(i, param.data())
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore if kvstore else False
        self._kv_initialized = True
        session = getattr(kvstore, "session", None)
        if session is not None:  # elastic store: bind the membership
            session.attach(self)  # session so step() absorbs bumps

    @property
    def learning_rate(self):
        return self._optimizer.lr_scheduler(self._optimizer.num_update) \
            if self._optimizer.lr_scheduler else self._optimizer.lr

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr) \
            if self._optimizer.lr_scheduler is None else None
        if self._optimizer.lr_scheduler is None:
            self._optimizer.lr = lr

    def step(self, batch_size, ignore_stale_grad=False):
        """ref: trainer.py:305 — allreduce + update.

        The step boundary is the telemetry heartbeat: step count/latency/
        throughput counters update here, a throttled memory sample is
        taken, and one metrics line goes to the MXNET_METRICS_EXPORT
        sink when configured (telemetry.record_step)."""
        import time as _time
        from .. import telemetry as _telemetry
        t0 = _time.perf_counter()
        if not self._kv_initialized:
            self._init_kvstore()
        if self._elastic is not None:
            self._elastic_step(batch_size)
        else:
            self._optimizer.rescale_grad = self._scale / batch_size
            self._allreduce_grads()
        self._update(ignore_stale_grad)
        if self._elastic is not None:
            self._elastic.note_step(batch_size)
        _telemetry.record_step(batch_size, _time.perf_counter() - t0)

    def _elastic_step(self, batch_size):
        """The zero-user-code elastic boundary: heartbeat, observe
        generation bumps, absorb a mid-exchange MembershipChanged by
        rebuilding with the survivors and re-exchanging the SAME
        gradients under the new generation (docs/resilience.md). The
        summed exchange is normalized by 1/(batch x world), i.e. the
        global-batch mean — shrinking the world keeps per-sample
        update math intact."""
        from ..elastic.membership import MembershipChanged
        ses = self._elastic
        if ses.heartbeat():
            ses.rebuild()  # clears buckets, rescales LR, replans
        while True:
            self._optimizer.rescale_grad = \
                self._scale / (batch_size * max(1, ses.world))
            try:
                self._allreduce_grads()
                return
            except MembershipChanged:
                ses.rebuild()

    def _on_membership_change(self, old_view, new_view):
        """Session rebuild hook: relayout the gradient buckets for the
        new world size, rescale the LR (linear-scaling rule, anchored
        at the reference world — MXELASTIC_LR_SCALE), and re-infer the
        shard plan's batch axis from the devices still present (the
        ShardPlan.from_manifest path, live)."""
        from .. import config
        self._grad_buckets = None  # relayout for the new world
        ses = self._elastic
        if ses is not None and config.get("MXELASTIC_LR_SCALE") and \
                ses._base_lr and self._optimizer.lr_scheduler is None:
            self._optimizer.lr = ses._base_lr * \
                new_view.world_size / float(ses.ref_world)
        plan = self._shard_plan
        if plan is not None and new_view is not None and \
                new_view.devices:
            try:
                import jax as _jax
                ids = set(new_view.device_ids())
                devs = [d for d in _jax.devices() if d.id in ids]
                if devs:
                    self._shard_plan = plan.reinfer(devices=devs)
            except Exception as e:  # a bad device map must not stop
                import warnings  # the rebuild — weights stay usable
                warnings.warn(
                    f"elastic rebuild: shard-plan re-inference failed "
                    f"({e}); keeping the previous plan")

    def allreduce_grads(self):
        """ref: trainer.py:334."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if self._update_on_kvstore or \
                not getattr(self._kvstore, "supports_flat_allreduce",
                            False):
            # server-side optimizer (or async PS): the server applies
            # per key — per-param push/pull semantics are the contract
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.push(i, param.list_grad(), priority=-i)
                    if not self._update_on_kvstore:
                        self._kvstore.pull(i, param.list_grad(),
                                           priority=-i)
            return
        self._allreduce_grads_bucketed()

    def _bucketable(self, param):
        """Dense single-buffer gradients coalesce; row_sparse grads and
        multi-device shard lists keep the per-param path."""
        from ..ndarray.sparse import RowSparseNDArray
        grads = param.list_grad()
        return len(grads) == 1 and \
            not isinstance(grads[0], RowSparseNDArray)

    def _allreduce_grads_bucketed(self):
        """DDP-style coalesced exchange (ISSUE 5): O(buckets) kvstore
        round trips instead of O(params) — gradients of like dtype are
        flattened into buckets capped at MXNET_GRAD_BUCKET_BYTES
        (step.buckets), allreduced flat, and scattered back into the
        parameters' grad buffers."""
        from ..ndarray.ndarray import _wrap
        from ..step.buckets import GradientBuckets
        items, leftover = [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if not self._bucketable(param):
                leftover.append(i)
                continue
            g = param.grad()
            items.append((i, tuple(g.shape), str(g.dtype),
                          g.size * g.dtype.itemsize))
        world = self._elastic.world if self._elastic is not None \
            else getattr(self._kvstore, "num_workers", 1)
        sig = (tuple(items), tuple(leftover), world)
        # (re)build when the layout changes — a Parameter.cast (amp
        # fine-tuning), grad_req flip, or elastic world-size change
        # would otherwise hit a stale assignment (mixed-dtype concat /
        # a layout whose round numbering belonged to a dead generation)
        if self._grad_buckets is None or self._grad_buckets[2] != sig:
            self._grad_buckets = (GradientBuckets(items,
                                                  world_size=world),
                                  leftover, sig)
        buckets, leftover, _ = self._grad_buckets
        grads = {i: self._params[i].grad()._data
                 for b in buckets.buckets for i, _, _ in b.entries}
        # exchange EVERY bucket before rebinding any: an elastic
        # MembershipChanged mid-exchange aborts the whole step's
        # reduce with no partial effect, so the retry after the
        # rebuild re-exchanges the ORIGINAL gradients — a per-bucket
        # rebind would feed already-reduced sums back into the retry
        # and double-count them (same invariant as
        # ElasticStepFunction._exchange_once)
        reduced_parts = []
        for bid, bucket in enumerate(buckets.buckets):
            flat = buckets.flatten(bucket, grads)
            reduced = self._kvstore.allreduce_flat(
                f"__grad_bucket_{bid}", _wrap(flat))
            reduced_parts.append((bucket, reduced._data))
        for bucket, flat in reduced_parts:
            for i, seg in buckets.unflatten(bucket, flat).items():
                self._params[i].grad()._rebind(seg)
        for i in leftover:  # sparse / multi-device: per-param exchange
            self._kvstore.push(i, self._params[i].list_grad(),
                               priority=-i)
            self._kvstore.pull(i, self._params[i].list_grad(),
                               priority=-i)

    def update(self, batch_size, ignore_stale_grad=False):
        """ref: trainer.py:366."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        updater = self._updaters[0]
        if self._kvstore and self._update_on_kvstore:
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.pull(i, param.list_data(), priority=-i)
            return
        live = [(i, param) for i, param in enumerate(self._params)
                if param.grad_req != "null"]
        if len(live) > 1 and updater.aggregate_updates:
            # aggregated multi-tensor update: the list-form Updater
            # chunks by MXNET_OPTIMIZER_AGGREGATION_SIZE and runs one
            # fused kernel call per chunk (optimizer.update_multi);
            # sparse/multi-precision fall back per-param inside it
            updater([i for i, _ in live],
                    [p.grad() for _, p in live],
                    [p.data() for _, p in live])
            return
        for i, param in live:
            updater(i, param.grad(), param.data())

    def fuse_step(self, net, loss_fn=None, shard_plan=None, **kwargs):
        """Compile this trainer's whole step into one donated XLA
        computation (mxnet_tpu.step.StepFunction): ``fused.step(x, y)``
        replaces the record/backward/step(batch) triple with a single
        dispatch, for optimizers with a functional fused_apply. It
        agrees with the eager loop bitwise wherever XLA compiles an op
        the same way inside one program and alone (test-enforced on
        XLA:CPU with its dot fusions off, tests/conftest.py), and to
        rounding otherwise — on the TPU the whole-program fusions
        round differently from op-by-op dispatch (chip_smoke.py states
        and checks the tolerance). The trainer keeps owning optimizer
        state (save_states/load_states and mxresil checkpoints see the
        post-update values).

        With ``shard_plan=`` (a :class:`mxnet_tpu.shard.ShardPlan`) —
        or ``MXSHARD_AUTO=1`` and more than one local device — the
        step compiles GSPMD-sharded over the plan's named mesh: batch
        sharded on the ``batch`` axis, optimizer state ZeRO-sharded,
        parameters tensor-sharded per the plan's ``param_specs``; the
        same user code, ``P("batch", "model")`` composition included.
        Checkpoints taken through this trainer record the plan in
        their manifest and reshard on restore (docs/sharding.md)."""
        from .. import config
        if config.get("MXTUNE_AUTO"):
            # mxtune auto-apply (docs/tuning.md): the best measured
            # step/opt config for THIS model+device+space, applied via
            # set_flag before the step traces; any key mismatch or
            # validation failure leaves defaults untouched
            from ..tune.apply import consult_train, signature_of
            consult_train(signature_of(net))
        if shard_plan is None:
            import jax as _jax
            if config.get("MXSHARD_AUTO") and len(_jax.devices()) > 1:
                from ..shard import ShardPlan
                shard_plan = ShardPlan.from_env()
        if shard_plan is not None:
            from ..shard import ShardedStepFunction
            self._shard_plan = shard_plan
            return ShardedStepFunction(net, loss_fn, trainer=self,
                                       shard_plan=shard_plan, **kwargs)
        kvs = self._kvstore_params.get("kvstore")
        if not self._kv_initialized and (
                getattr(kvs, "session", None) is not None
                or (isinstance(kvs, str) and "elastic" in kvs)):
            self._init_kvstore()  # an elastic kvstore attaches here
        if self._elastic is not None:
            # elastic membership: the split-phase step whose update
            # program re-keys exactly once per world-size change
            from ..elastic.stepfn import ElasticStepFunction
            self._shard_plan = None
            return ElasticStepFunction(net, loss_fn, trainer=self,
                                       **kwargs)
        from ..step import StepFunction
        self._shard_plan = None  # an unsharded rebuild clears the plan
        return StepFunction(net, loss_fn, trainer=self, **kwargs)

    def save_states(self, fname):
        """ref: trainer.py save_states."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
        param_dict = {i: param for i, param in enumerate(self._params)}
        self._optimizer.param_dict = param_dict
