"""StepFunction: one donated XLA computation per training step.

The reference-shaped training loop runs four phases per step — forward,
backward, gradient exchange, optimizer update — as separate dispatch
streams: the gluon ``Trainer`` pushes/pulls one kvstore key per
parameter and calls one ``Optimizer.update`` per parameter, each a
separate un-jitted dispatch (ref: python/mxnet/gluon/trainer.py:305).
``StepFunction`` captures all four into ONE ``jax.jit`` computation —
one dispatch per step instead of O(params):

- forward + backward via ``jax.vjp`` over the same pure trace the
  hybridize/Executor machinery uses (``gluon.block.functional_call``
  for HybridBlocks, ``executor.graph_forward_backward`` for Symbols),
  seeded with a ones cotangent exactly like ``loss.backward()``;
- gradient exchange lowered in-jit: identity for the single-process
  path, ``lax.psum`` over ``psum_axis`` when the step runs inside a
  mesh context (the cross-replica phase is part of the fused program,
  per "Automatic Cross-Replica Sharding of Weight Update");
- the optimizer via the functional multi-tensor
  :meth:`~mxnet_tpu.optimizer.Optimizer.fused_apply` kernels. Per-step
  scalars (lr, wd, Adam bias correction) are computed on the host in
  float64 — the exact arithmetic of the eager per-param loop — and
  passed as ONE host ``float32`` array of shape ``(2, leaves)`` (no
  device program per scalar; its shape never changes, so schedulers
  never retrace), unpacked inside the trace into each leaf's dtype;
- weight and optimizer-state buffers **donated** to XLA (buffer
  reuse); the post-step write-back rebinds the gluon Parameters and
  the Updater states in place, so checkpoints, kvstore updaters and
  ``mxresil`` preemption guards observe the post-update values.

The fused step is **bitwise-identical** to the eager loop wherever XLA
compiles an op the same way inside one program and alone
(test-enforced for SGD/Adam/AdamW in tests/test_step.py on XLA:CPU
with its dot fusions off, tests/conftest.py); elsewhere — the TPU's
whole-program fusions — it agrees to rounding. Two mechanisms keep the
update itself exact: the eager per-param path dispatches each
optimizer kernel as one jitted program (optimizer._jk — the same
expression DAG XLA sees inside the fused step, so FMA contraction
applies equally to both), and an ``optimization_barrier`` pins the
gradient/update boundary so fusion cannot clone gradient producers
into the update kernels with different contraction.

Compiled programs are keyed by the input shape signature; hits/misses
feed the telemetry registry (``fused_step_cache_hits_total`` /
``..._misses_total``) and every miss is classified by the recompile
auditor (kind ``fused_step``) — ``tools/mxprof.py step`` renders the
report. See docs/performance.md.
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as onp

from .. import optimizer as opt_mod
from ..base import MXNetError
from ..ndarray.ndarray import NDArray, _wrap
from ..optimizer import _state_rebind, _state_values
from .. import random as _random

__all__ = ["StepFunction"]


def _raw(a):
    return a._data if isinstance(a, NDArray) else jnp.asarray(a)


def _unpack_hyper(hyper, weights):
    """The ``(2, leaves)`` array of :meth:`StepFunction._hyper`, inside
    the trace, as the two lists ``fused_apply`` takes. Each leaf's pair
    is cast to that leaf's dtype: a scalar sliced out of an f32 array is
    strongly typed, and ``lr * g`` with a bf16 ``g`` would come out f32
    where the eager loop's python float keeps it bf16 — the cast makes
    the product the one the eager kernels compute, bit for bit (they
    use ``lr``/``wd`` only as scalar x array of the weight's dtype)."""
    lrs = [hyper[0, k].astype(w.dtype) for k, w in enumerate(weights)]
    wds = [hyper[1, k].astype(w.dtype) for k, w in enumerate(weights)]
    return lrs, wds


class StepFunction:
    """Fused whole-train-step compiler for a HybridBlock (or Symbol).

    Block mode::

        trainer = gluon.Trainer(net.collect_params(), "sgd", {...})
        fused = StepFunction(net, loss_fn, trainer=trainer)
        for x, y in batches:
            loss = fused.step(x, y)          # ONE dispatch

    is the fused equivalent of::

        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch_size)

    and equal to it (bitwise under the condition in the module
    docstring) for every optimizer with a functional ``fused_apply``
    (SGD/NAG/Adam/AdamW/RMSProp). Without a trainer,
    pass ``optimizer=``/``optimizer_params=`` and the StepFunction owns
    its own Updater (state lives in ``self.updater.states`` — the same
    structure ``Trainer.save_states`` snapshots).

    Symbol mode::

        fused = StepFunction(loss_sym, arg_dict=args, aux_dict=auxs,
                             input_names=("data", "label"),
                             optimizer="sgd")

    traces the symbol through the Executor's ``eval_graph`` machinery
    (``executor.graph_forward_backward``); the symbol's first output is
    the per-sample loss.
    """

    # a Parameter shared between blocks is one leaf of this step; a
    # subclass whose placement or exchange goes by name refuses it
    _ties_shared = True

    def __init__(self, net, loss_fn=None, trainer=None, optimizer="sgd",
                 optimizer_params=None, arg_dict=None, aux_dict=None,
                 input_names=("data", "softmax_label"), grad_names=None,
                 donate=True, psum_axis=None, name=None):
        from ..symbol.symbol import Symbol
        self._net = net
        self._loss_fn = loss_fn
        self._trainer = trainer
        self._psum_axis = psum_axis
        self._symbol_mode = isinstance(net, Symbol)
        self._name = name or (net.name if hasattr(net, "name")
                              else type(net).__name__)
        # donation is a no-op on the CPU backend (and jax warns about
        # it per compile); request it only where PJRT honors it
        self._donate = bool(donate) and jax.default_backend() != "cpu"
        self._cache = {}
        self._last = None  # (jitted fn, key) of the newest compile
        self._opt_report = None  # graph-optimizer report (symbol mode)
        self._opt_level = 0
        # mxguard integrity taps (mxnet_tpu/guard/): fingerprints ride
        # as extra outputs of the SAME compiled program when MXGUARD is
        # on (or a Monitor tic forces them); the flag is part of the
        # signature-cache key so flipping it re-keys visibly and the
        # steady state stays at zero recompiles either way
        self._nstep = 0
        self._guard_probe = None  # per-instance EWMA anomaly probe
        self._recorder = None  # guard.ReplayRecorder (attach_recorder)
        self._monitor_cb = None  # Monitor duck-type (set_monitor_...)
        self._monitor_all = False
        self._last_fps = None  # (2+n_grads, 3) of the last noted step
        self._pending_guard = None  # deferred (fps, loss, step) note
        self._fp_names = ()
        self._last_loss = None
        self.guard_events = []  # vote/self-check verdicts (elastic)

        if trainer is not None:
            if optimizer_params or optimizer != "sgd":
                raise MXNetError("pass either trainer= or optimizer=/"
                                 "optimizer_params=, not both")
            self._optimizer = trainer._optimizer
            self._updater = trainer._updaters[0]
            self._scale = trainer._scale
            if (trainer._kvstore_params.get("update_on_kvstore")
                    or (trainer._kv_initialized
                        and trainer._update_on_kvstore)):
                raise MXNetError(
                    "StepFunction runs the optimizer inside the fused "
                    "step; update_on_kvstore trainers are unsupported — "
                    "create the Trainer with update_on_kvstore=False (or "
                    "no kvstore)")
            kvs = trainer._kvstore_params.get("kvstore")
            kv_type = getattr(kvs, "type",
                              kvs if isinstance(kvs, str) else "")
            if isinstance(kv_type, str) and "dist" in kv_type:
                raise MXNetError(
                    "StepFunction does not drive the kvstore data "
                    "plane; for multi-process training use "
                    "parallel.ParallelTrainer (in-jit psum over a "
                    "mesh) or the eager Trainer loop")
        else:
            self._optimizer = opt_mod.create(optimizer,
                                             **(optimizer_params or {}))
            self._updater = opt_mod.get_updater(self._optimizer)
            self._scale = 1.0

        if self._optimizer.multi_precision:
            raise MXNetError("StepFunction does not support "
                             "multi_precision optimizers; use the eager "
                             "per-param path")
        if not self._optimizer.has_fused_apply:
            raise MXNetError(
                f"optimizer {type(self._optimizer).__name__} has no "
                "functional fused_apply — the fused step would downgrade "
                "to eager; implement fused_apply (see steplint) or use "
                "the eager Trainer loop")
        if trainer is not None:
            # ALL validation passed — only now alter the trainer: the
            # fused step replaces the kvstore data plane, so a later
            # trainer.step() must not double-apply through a
            # server-side optimizer
            trainer._kvstore_params["update_on_kvstore"] = False

        if self._symbol_mode:
            self._init_symbol(net, arg_dict or {}, aux_dict or {},
                              tuple(input_names), grad_names)
        else:
            self._plist = None  # resolved lazily (deferred shapes)

    # ------------------------------------------------------------------
    # parameter resolution
    # ------------------------------------------------------------------
    def _init_symbol(self, sym, arg_dict, aux_dict, input_names,
                     grad_names):
        # bind-time graph optimization (MXNET_GRAPH_OPT): the fused
        # step traces the OPTIMIZED symbol — and because the rewrite
        # pipeline preserves the binding surface, the sharded subclass
        # composes unchanged (same in/out shardings over the optimized
        # graph; the plan never names interior nodes). The report is
        # keyed into _shard_key so flipping the level between
        # constructions can never alias a cached program.
        from ..base import get_env
        self._opt_report = None
        self._opt_level = 0
        if get_env("MXNET_GRAPH_OPT", 0):
            from ..opt import optimize_symbol, opt_level
            self._opt_level = opt_level()
            sym, self._opt_report = optimize_symbol(
                sym, where=f"StepFunction:{self._name}")
            self._net = sym
        self._input_names = tuple(input_names)
        missing = [n for n in sym.list_arguments()
                   if n not in arg_dict and n not in self._input_names]
        if missing:
            raise MXNetError(f"symbol-mode StepFunction: arg_dict is "
                             f"missing {missing}")
        self._param_objs = dict(arg_dict)
        self._aux_objs = {n: aux_dict[n]
                          for n in sym.list_auxiliary_states()}
        self._trainable = tuple(sorted(grad_names if grad_names is not None
                                       else self._param_objs))
        self._indices = list(range(len(self._trainable)))
        self._ensure_states({i: self._param_objs[n]
                             for i, n in zip(self._indices,
                                             self._trainable)})

    def _resolve_block_params(self, sample_x):
        from ..gluon.parameter import DeferredInitializationError
        try:
            plist = sorted(
                self._net._collect_params_with_prefix().items())
            for _, p in plist:
                p.data()
        except DeferredInitializationError:
            from .. import autograd as _ag
            from ..trace import setup as _setup
            with _ag.pause(), _setup.eager("step.resolve", fn=self._name):
                self._net(_wrap(_raw(sample_x)[:1]))
            plist = sorted(
                self._net._collect_params_with_prefix().items())
        # weight tying: one Parameter under several prefixed names (an
        # embedding that is also the head) is ONE leaf of the step,
        # under the first of its names. ``functional_call`` binds the
        # Parameter object, so every use reads that one traced value:
        # the leaf's gradient is the sum over its uses, it is updated
        # once and its update count advances once, as in the eager
        # loop. (A leaf a name would split the gradient across the
        # aliases and update each from the same pre-step weight.)
        by_id, tied = {}, []
        for n, p in plist:
            if id(p) in by_id:
                tied.append((p.name, by_id[id(p)], n))
            else:
                by_id[id(p)] = n
        if tied and not self._ties_shared:
            name, first, alias = tied[0]
            raise MXNetError(
                f"{type(self).__name__}: parameter '{name}' is shared "
                f"between blocks (as '{first}' and '{alias}'); "
                "weight-tied models are not supported by this step — "
                "use StepFunction or the eager record/backward/step "
                "loop")
        plist = [(n, p) for n, p in plist if by_id[id(p)] == n]
        self._plist = plist
        self._param_objs = {n: p for n, p in plist}
        if self._trainer is not None:
            index_of = self._trainer._param2idx
            trainable = [(n, p) for n, p in plist
                         if p.name in index_of and p.grad_req != "null"]
            self._indices = [index_of[p.name] for _, p in trainable]
        else:
            trainable = [(n, p) for n, p in plist if p.grad_req != "null"]
            self._indices = list(range(len(trainable)))
            self._optimizer.param_dict = {
                i: p for i, (_, p) in zip(self._indices, trainable)}
        self._trainable = tuple(n for n, _ in trainable)
        for n, p in trainable:
            if p.grad_req == "add":
                warnings.warn(
                    f"StepFunction: parameter {p.name} has grad_req="
                    "'add'; the fused step computes fresh per-step "
                    "gradients (accumulation is not folded in)")
        self._ensure_states({i: p for i, (_, p) in zip(self._indices,
                                                       trainable)})
        self._psig = tuple(p.grad_req for _, p in plist)

    def _param_dtypes(self):
        """Parameter dtype signature for the cache key: a mid-run
        Parameter.cast retraces jax's jit internally, and without the
        dtypes in OUR key the retrace would be miscounted as a cache
        hit and stay invisible to the recompile auditor."""
        if self._symbol_mode:
            return tuple(str(v._data.dtype)
                         for _, v in sorted(self._param_objs.items()))
        return tuple(str(p.data()._data.dtype) for _, p in self._plist)

    def _ensure_states(self, by_index):
        upd = self._updater
        for i, p in by_index.items():
            if i not in upd.states:
                w = p.data() if hasattr(p, "data") else p
                upd.states[i] = \
                    self._optimizer.create_state_multi_precision(i, w)
                upd.states_synced[i] = True

    # ------------------------------------------------------------------
    # tracing
    # ------------------------------------------------------------------
    def _exchange(self, grads):
        """Gradient exchange, lowered into the jit: identity for the
        single-process path, psum over a named mesh axis otherwise."""
        if self._psum_axis is None:
            return grads
        with jax.named_scope("exchange"):
            return jax.tree.map(
                lambda g: jax.lax.psum(g, self._psum_axis), grads)

    def _apply(self, trainable_vals, grads, svals, hyper):
        """The in-jit update segment: exchange + fused multi-tensor
        optimizer (``hyper``: :meth:`_hyper`'s array). The barrier pins
        the gradient/update boundary so XLA's producer-consumer fusion
        cannot clone gradient expressions into the update kernels with
        different FMA contraction — the bitwise-parity contract with
        the eager loop (whose per-param kernels jit the same expression
        DAG)."""
        grads = jax.lax.optimization_barrier(grads)
        grads = self._exchange(grads)
        weights = [trainable_vals[n] for n in self._trainable]
        lrs, wds = _unpack_hyper(hyper, weights)
        return self._optimizer.fused_apply(
            self._indices, weights,
            [grads[n] for n in self._trainable], svals, lrs, wds)

    def _build_grads(self, taps=False):
        """Pure ``(pvals, inputs, rng) -> (grads, extras, loss)``
        builder — the forward+backward phase shared by the one-program
        step and the elastic split-phase step (mxnet_tpu/elastic/
        stepfn.py, which exchanges gradients host-side between this
        and the update program). ``extras`` is the non-gradient state
        the step must write back (BN running stats; the symbol graph's
        ``__aux__`` dict).

        ``taps=True`` (mxguard) appends a fourth output: the
        fingerprint matrix — row 0 the fold over the pre-step
        trainable weights (bitwise-replicated across data-parallel
        workers, the exact-majority vote row), rows 1..n one
        (checksum, absmax, nonfinite) triple per gradient in sorted
        trainable order, and a final LOCAL loss row
        ``(mean, absmax, nonfinite)`` so the anomaly probe needs no
        second device fetch (the loss row never enters the
        cross-replica vote — losses legitimately differ per worker).
        The gradients pass through an ``optimization_barrier`` before
        being fingerprinted AND before the update consumes them, so
        the gradient producers see the same single consumer with taps
        on or off — the taps-on step is bitwise-identical in weights
        to taps-off (test-enforced)."""
        base = self._build_grads_base()
        if not taps:
            return base
        trainable = self._trainable
        from ..guard.fingerprint import fingerprint_rows, fold_rows

        def tapped(pvals, inputs, rng):
            grads, extras, lout = base(pvals, inputs, rng)
            grads = jax.lax.optimization_barrier(grads)
            prow = fold_rows(fingerprint_rows(
                pvals[n] for n in trainable))
            grows = fingerprint_rows(grads[n] for n in trainable)
            lflat = jnp.asarray(lout).astype(jnp.float32).reshape(-1)
            lrow = jnp.stack([
                jnp.mean(lflat), jnp.max(jnp.abs(lflat)),
                jnp.sum(~jnp.isfinite(lflat)).astype(jnp.float32)])
            fps = jnp.concatenate(
                [prow[None, :], grows, lrow[None, :]], axis=0)
            return grads, extras, lout, fps

        return tapped

    def _build_grads_base(self):
        if self._symbol_mode:
            sym = self._net
            trainable = self._trainable
            input_names = self._input_names
            from ..executor import graph_forward_backward
            fb = graph_forward_backward(sym, list(trainable))

            def pure_grads(pvals, inputs, rng):
                arg_vals = dict(pvals)
                arg_vals.update(zip(input_names, inputs))
                aux_vals = dict(arg_vals.pop("__aux__", {}))
                outs, aux_updates, grads = fb(
                    arg_vals, aux_vals, rng,
                    tuple([None] * len(sym._outputs)))
                return grads, {"__aux__": dict(aux_updates)}, outs[0]

            return pure_grads

        block, loss_fn = self._net, self._loss_fn
        trainable = self._trainable
        from ..gluon.block import functional_call

        def pure_grads(pvals, inputs, rng):
            # the scope names the phases in the program's metadata:
            # jax writes the forward pass as ``jvp(forward)/...`` and
            # the backward pass as ``transpose(jvp(forward))/...``
            # (_build_pure adds ``optimizer``), which is what a profile
            # and the benchmark's per-phase readers tell them by
            def loss_of(tvals):
                allp = dict(pvals)
                allp.update(tvals)
                with jax.named_scope("forward"):
                    (out,), aux = functional_call(
                        block, allp, [_wrap(inputs[0])], training=True,
                        rng_raw=rng)
                    if loss_fn is None:
                        lout = out
                    else:
                        louts, _ = functional_call(
                            loss_fn, {},
                            [_wrap(out)] + [_wrap(v)
                                            for v in inputs[1:]],
                            training=True)
                        lout = louts[0]
                return lout, aux

            tvals = {n: pvals[n] for n in trainable}
            lout, vjp_fn, aux = jax.vjp(loss_of, tvals, has_aux=True)
            grads = vjp_fn(jnp.ones_like(lout))[0]
            return grads, aux, lout  # aux: BN running stats

        return pure_grads

    def _build_pure(self, guard=False):
        """The whole-step program: grads + exchange + fused update in
        one trace (the expression DAG is unchanged by the _build_grads
        factoring — bitwise parity with the eager loop holds). With
        ``guard`` the fingerprint matrix rides as a fourth output."""
        grads_fn = self._build_grads(taps=guard)
        trainable = self._trainable

        def pure_step(pvals, svals, hyper, inputs, rng):
            out = grads_fn(pvals, inputs, rng)
            grads, extras, lout = out[:3]
            tvals = {n: pvals[n] for n in trainable}
            with jax.named_scope("optimizer"):
                new_w, new_s = self._apply(tvals, grads, svals, hyper)
            new_params = dict(pvals)
            new_params.update(zip(trainable, new_w))
            new_params.update(extras)
            if guard:
                return new_params, new_s, lout, out[3]
            return new_params, new_s, lout

        return pure_step

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def _make_jit(self, pure, guard=False):
        """Compile hook: the sharded subclass (mxnet_tpu/shard/)
        overrides this to attach NamedSharding in/out annotations over
        its device mesh (``guard`` tells it the program carries the
        extra fingerprint output); the base step is
        single-(logical-)device."""
        return jax.jit(pure,
                       donate_argnums=(0, 1) if self._donate else ())

    def _shard_key(self):
        """Extra cache-key component for subclasses whose compiled
        program depends on more than shapes/dtypes/optimizer scalars
        (the sharded step keys on its plan fingerprint)."""
        return ()

    def _hyper(self):
        """Per-step scalar hyperparameters, host-computed (float64 —
        the eager loop's arithmetic, advancing the update counts), as
        ONE host ``float32`` array ``(2, leaves)``: row 0 the rates,
        row 1 the weight decays. jit moves it in one transfer; a device
        scalar a leaf would be a program a leaf. Shape and dtype never
        change, so value changes (schedulers, Adam's t) never
        retrace."""
        hyper = onp.zeros((2, len(self._indices)), onp.float32)
        for k, i in enumerate(self._indices):
            hyper[:, k] = self._optimizer.fused_hyper(i)
        return hyper

    def _gather(self):
        if self._symbol_mode:
            pvals = {n: v._data for n, v in self._param_objs.items()}
            pvals["__aux__"] = {n: v._data
                                for n, v in self._aux_objs.items()}
        else:
            pvals = {n: p.data()._data for n, p in self._plist}
        svals = [_state_values(self._updater.states[i])
                 for i in self._indices]
        return pvals, svals

    def _writeback(self, new_params, new_states):
        if self._symbol_mode:
            aux = new_params.pop("__aux__", {})
            for n, v in aux.items():
                if n in self._aux_objs:
                    self._aux_objs[n]._rebind(v)
            for n, v in new_params.items():
                self._param_objs[n]._rebind(v)
        else:
            for n, v in new_params.items():
                p = self._param_objs.get(n)
                if p is not None:
                    p.data()._rebind(v)
        for i, ns in zip(self._indices, new_states):
            _state_rebind(self._updater.states[i], ns)

    def _prepare(self, inputs):
        """Resolve parameters (and re-derive the trainable set on a
        grad_req flip) before keying/compiling — shared with the
        elastic split-phase step."""
        if not self._symbol_mode:
            if self._plist is None:
                self._resolve_block_params(inputs[0])
            elif self._psig != tuple(p.grad_req
                                     for _, p in self._plist):
                # grad_req flipped mid-run (freeze/unfreeze): the
                # trainable set — and hence the program — changed;
                # re-derive it (the eager loop picks this up
                # implicitly, so the fused step must too)
                self._resolve_block_params(inputs[0])
                self._cache.clear()

    def _miss_signature_extra(self):
        """Non-shape signature keys for the recompile record —
        subclasses whose cache key carries more than shapes/dtypes
        (the sharded step's plan fingerprint) report them here so the
        auditor classifies their re-keys as ``key-change`` instead of
        cache eviction."""
        return {}

    def _record_miss(self, inputs):
        """Count + classify one signature-cache miss (the recompile
        auditor's fused_step kind)."""
        from ..telemetry import metrics as _metrics
        from ..telemetry import recompile as _recompile
        _metrics.counter(
            "fused_step_cache_misses_total",
            "fused-step signature-cache misses (compiles)").inc()
        sig = _recompile.signature_of([_wrap(v) for v in inputs], True)
        sig.update(self._miss_signature_extra())
        _recompile.record_recompile(
            f"StepFunction:{self._name}", sig, kind="fused_step")

    def step(self, x, *labels, batch_size=None, rng_raw=None):
        """Run one fused training step; returns the loss NDArray.
        ``rng_raw`` overrides the step's RNG key data — the
        deterministic-replay hook (mxnet_tpu/guard/replay.py)."""
        from ..telemetry import metrics as _metrics
        from .. import telemetry as _telemetry
        from .. import trace as _trace
        t0 = time.perf_counter()
        inputs = tuple(_raw(a) for a in (x,) + labels)
        self._prepare(inputs)
        if batch_size is None:
            batch_size = int(inputs[0].shape[0]) if inputs[0].ndim else 1
        self._optimizer.rescale_grad = self._scale / batch_size
        guard = self._guard_enabled()

        # the per-step trace root (serving's serve.request analog):
        # prep/dispatch/writeback decompose as children, keyed by step
        # number so mxprof trace correlates across subsystems; a miss
        # adds the program's trace/lower/compile under the dispatch.
        # The tree counts its thread's CPU time (cpu_ns), so that wall
        # less CPU tells a host that waits inside the runtime from one
        # that computes
        with _trace.span("train.step", "train", cpu=True,
                         step=self._nstep, fn=self._name,
                         kind=type(self).__name__):
            # key on input signature + parameter dtypes + every scalar
            # the trace bakes in (rescale_grad, clip, momentum, betas,
            # ... — fused_signature), so mid-run hyperparameter
            # mutation and Parameter.cast retrace VISIBLY (counted as
            # misses, recorded by the recompile auditor) instead of
            # silently. The mxguard tap flag re-keys the same way
            # (taps are extra outputs of the program — a different
            # program).
            key = (tuple((tuple(v.shape), str(v.dtype))
                         for v in inputs),
                   self._param_dtypes(), self._opt_level, guard,
                   self._optimizer.fused_signature()) \
                + self._shard_key()
            fn = self._cache.get(key)
            missed = fn is None
            if missed:
                self._record_miss(inputs)
                # lazy: jax traces, lowers and compiles it (or reads it
                # from the persistent cache) in the first dispatch
                fn = self._make_jit(self._build_pure(guard), guard)
                self._cache[key] = fn
                self._last = (fn, key)
            else:
                _metrics.counter(
                    "fused_step_cache_hits_total",
                    "fused-step signature-cache hits").inc()

            with _trace.span("step.prep", "train"):
                with _trace.span("step.prep.hyper", "train",
                                 leaves=len(self._indices)):
                    hyper = self._hyper()
                with _trace.span("step.prep.gather", "train") as sp:
                    pvals, svals = self._gather()
                    if sp.sampled:
                        sp.set(leaves=len(jax.tree.leaves((pvals,
                                                           svals))))
                with _trace.span("step.prep.rng", "train"):
                    rng = jnp.asarray(rng_raw) if rng_raw is not None \
                        else jax.random.key_data(_random.next_key())
            t1 = time.perf_counter()
            with _trace.span("step.dispatch", "train",
                             batch=batch_size):
                if missed:
                    # the builds inside are the step program's:
                    # step.trace / step.lower / step.compile
                    from ..trace import setup as _setup
                    with _setup.step_program():
                        out = fn(pvals, svals, hyper, inputs, rng)
                else:
                    out = fn(pvals, svals, hyper, inputs, rng)
            new_params, new_states, loss = out[:3]
            t2 = time.perf_counter()
            with _trace.span("step.writeback", "train"):
                self._writeback(new_params, new_states)
                if guard:
                    if self._recorder is not None or self._monitor_all:
                        # recorder/monitor consumers need THIS step's
                        # values (an earlier deferred note flushes
                        # first — the probe must observe steps in
                        # order)
                        self._flush_pending_guard()
                        self._guard_note(out[3], loss, inputs, rng)
                    else:
                        # telemetry-only mode: defer the host read one
                        # step — by the next boundary the program has
                        # completed, so the fetch copies a finished
                        # buffer instead of stalling the async
                        # pipeline (the measured tap overhead is the
                        # in-program reductions alone)
                        self._flush_pending_guard()
                        self._pending_guard = (out[3], loss,
                                               self._nstep)
            t3 = time.perf_counter()
        _metrics.histogram(
            "fused_step_host_seconds",
            "fused-step host prep (hyper scalars + buffer gather)"
            ).observe(t1 - t0)
        _metrics.histogram(
            "fused_step_dispatch_seconds",
            "fused-step compiled-call dispatch (async; excludes device "
            "wait)").observe(t2 - t1)
        _metrics.histogram(
            "fused_step_writeback_seconds",
            "fused-step parameter/state rebind").observe(t3 - t2)
        _telemetry.record_step(batch_size, time.perf_counter() - t0)
        self._nstep += 1
        return _wrap(loss)

    __call__ = step

    # ------------------------------------------------------------------
    # mxguard integrity taps (mxnet_tpu/guard/; docs/resilience.md)
    # ------------------------------------------------------------------
    def _guard_enabled(self) -> bool:
        """Taps on: the MXGUARD flag, or a Monitor tic for this step
        (``_monitor_all`` — the reference executor's monitor switch,
        set by ``Monitor.tic``)."""
        from .. import config
        return bool(config.get("MXGUARD")) or self._monitor_all

    def attach_recorder(self, recorder):
        """Attach a :class:`~mxnet_tpu.guard.replay.ReplayRecorder`:
        every guarded step records its batch digests, RNG key, hyper
        scalars, loss digest and fingerprints into the bounded ring."""
        self._recorder = recorder
        return recorder

    @property
    def guard_probe(self):
        """This step function's OWN EWMA anomaly probe (lazy): each
        in-process worker keeps its own loss/step stream, so replay
        windows attribute to the right run. Register on a watchdog
        via ``wd.add_probe(fused.guard_probe.check)`` — or
        ``guard.anomaly.check_all`` to cover every probe at once."""
        if self._guard_probe is None:
            from ..guard.anomaly import GuardProbe
            self._guard_probe = GuardProbe(name=self._name)
        return self._guard_probe

    @property
    def last_fingerprints(self):
        """The newest tap matrix ``(params, *grads, loss) x (checksum,
        absmax, nonfinite)`` — materializes a deferred note first, so
        readers always see the LAST COMPLETED step's values."""
        self._flush_pending_guard()
        return self._last_fps

    def flush_guard(self):
        """Process any deferred tap note NOW (telemetry-only mode
        reads the previous step's completed buffers; call this after
        the final step of a run, or before reading guard telemetry
        that must include the newest step)."""
        self._flush_pending_guard()
        return self._last_fps

    def _flush_pending_guard(self):
        if self._pending_guard is None:
            return
        fps, loss, step = self._pending_guard
        self._pending_guard = None
        self._guard_note(fps, loss, None, None, step=step)

    def _guard_note(self, fps, loss_raw, inputs, rng,
                    good: bool = True, strict: bool = True,
                    step: Optional[int] = None):
        """Post-step guard bookkeeping shared with the elastic
        subclass: publish the fingerprints, feed the EWMA anomaly
        probe, run the solo strict check, and record the replay ring
        entry."""
        import numpy as onp
        from .. import config
        if step is None:
            step = self._nstep
        # ONE device fetch: the matrix carries the loss row too, so
        # the probe never forces a second transfer (the recorder —
        # opt-in — is the only consumer that touches the loss buffer)
        fps_host = onp.asarray(fps, dtype=onp.float32)
        self._last_fps = fps_host
        self._fp_names = ("__params__",) + self._trainable \
            + ("__loss__",)
        self._last_loss = loss_raw
        n_grads = len(self._trainable)
        loss_row = fps_host[-1]
        loss_mean = float(loss_row[0]) if not loss_row[2] \
            else float("nan")
        grad_absmax = float(fps_host[1:1 + n_grads, 1].max()) \
            if n_grads else None
        anomaly = self.guard_probe.observe(step, loss_mean,
                                           grad_absmax)
        nonfinite = float(fps_host[1:1 + n_grads, 2].sum()) \
            if n_grads else 0.0
        if nonfinite and strict and config.get("MXGUARD_STRICT"):
            # the one-program fused step already applied the update
            # (grads and weights live in ONE donated program), so a
            # transparent retry is impossible here — hard-fail and
            # point at the replay ring. The split-phase elastic step
            # classifies/retries instead (guard/voting.py).
            from ..guard.voting import GuardCorruption
            raise GuardCorruption(step,
                                  [f"nonfinite:{int(nonfinite)}"])
        if self._recorder is not None and inputs is not None:
            scalars = {"rescale": float(self._optimizer.rescale_grad)}
            self._recorder.record(
                step, inputs, rng, onp.asarray(loss_raw),
                fps_host, scalars=scalars, trainer=self._trainer,
                good=good and anomaly is None and not nonfinite)

    def guard_state(self) -> Dict[str, object]:
        """The guardlint surface: what protection THIS step function
        actually has wired (docs/resilience.md integrity section)."""
        from .. import config
        rec = self._recorder
        return {"kind": type(self).__name__,
                "name": self._name,
                "taps": bool(config.get("MXGUARD")),
                "recorder": rec is not None,
                "ring_checkpoints": bool(
                    rec is not None and rec.has_checkpoint_ring),
                "exchanges_gradients": False,
                "guard_events": len(self.guard_events)}

    # -- Monitor duck-type (the executor monitor surface, so
    # ``Monitor.install(fused)`` works on the fused-step path — the
    # eager executor never runs there and per-op activations do not
    # exist as materialized values inside one XLA program; what the
    # monitor observes are the fingerprint taps + the loss) ------------
    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_cb = callback
        self._monitor_all = bool(monitor_all)

    def collect_monitor_stats(self, helper):
        """Feed the last step's tap values to a Monitor stat helper:
        one (3,) fingerprint NDArray per gradient (named
        ``<param>_grad_fp``), the params-digest row, and the loss."""
        if self.last_fingerprints is None:
            return
        for name, row in zip(self._fp_names, self.last_fingerprints):
            tag = "params_fp" if name == "__params__" \
                else "loss_fp" if name == "__loss__" \
                else f"{name}_grad_fp"
            helper(tag, _wrap(jnp.asarray(row)))
        if self._last_loss is not None:
            helper("loss", _wrap(jnp.asarray(self._last_loss)))

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    @property
    def opt_report(self):
        """Graph-optimizer report for symbol mode (None when off or in
        block mode — the optimizer works on the Symbol IR)."""
        return self._opt_report

    def cache_info(self) -> Dict[str, int]:
        from ..telemetry import metrics as _metrics
        return {
            "programs": len(self._cache),
            "hits": _metrics.counter(
                "fused_step_cache_hits_total").value(),
            "misses": _metrics.counter(
                "fused_step_cache_misses_total").value(),
        }

    def compiled(self, x, *labels):
        """The newest step program as a jax ``Compiled``: lowered with
        the CURRENT buffers (a persistent-cache hit when the step
        already ran); does not execute or donate. ``as_text()`` is the
        post-optimization HLO, ``memory_analysis()`` and
        ``cost_analysis()`` XLA's own accounting. Runs under the span
        ``step.aot``; what it builds feeds no set-up instrument."""
        from ..trace import setup as _setup
        if self._last is None:
            raise MXNetError("no compiled step yet — call step() first")
        fn, _ = self._last
        # the batch by shape only: where it is placed is the step's
        # business (the sharded step moves it onto its mesh)
        inputs = tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for v in map(_raw, (x,) + labels))
        hyper = onp.zeros((2, len(self._indices)), onp.float32)
        with _setup.aot():
            pvals, svals = self._gather()
            rng = jax.random.key_data(jax.random.key(0))
            return fn.lower(pvals, svals, hyper, inputs, rng).compile()

    def cost_analysis(self, x, *labels):
        """XLA cost analysis of the compiled step (bench roofline,
        mxtune cost-model features): a stable, JSON-serializable dict —
        sorted keys, plain floats only, always containing ``flops`` and
        ``bytes accessed``."""
        cost = self.compiled(x, *labels).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        # backend cost dicts leak device objects and odd scalar types;
        # keep only what float() accepts so the result round-trips
        # through json (mxtune persists these as model features)
        out = {}
        for k, v in (cost or {}).items():
            try:
                out[str(k)] = float(v)
            except (TypeError, ValueError):
                continue
        out.setdefault("flops", 0.0)
        out.setdefault("bytes accessed", 0.0)
        return dict(sorted(out.items()))
