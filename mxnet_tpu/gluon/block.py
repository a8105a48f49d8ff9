"""Gluon Block / HybridBlock.

ref: python/mxnet/gluon/block.py — Block :131, HybridBlock :705 (whose
_build_cache :786 captures the graph into a CachedOp, ref:
src/imperative/cached_op.cc), SymbolBlock :992.

TPU-native hybridize: instead of tracing with Symbol proxies into an NNVM
graph executed by CachedOp's static/dynamic paths, `hybridize()` wraps the
block's forward in jax.jit. The compiled function takes (param values,
input values, rng key) and returns (outputs, mutated-state updates), so:
- static_alloc/static_shape semantics are XLA's default (preallocated
  buffers, shape-specialized executable — ref: cached_op.cc StaticForward);
- randomness stays fresh across calls (key is an argument);
- BatchNorm-style running stats flow out functionally and are written back
  (the aux-state story, ref: batch_norm.cc aux).
Autograd through a hybridized call records ONE tape node whose vjp is the
compiled function's vjp — the analog of CachedOp::Backward (:1128).
"""
from __future__ import annotations

import contextlib
import re
import threading
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as onp

from .. import autograd
from .. import random as _random
from ..base import MXNetError
from ..context import current_context
from ..ndarray.ndarray import NDArray, _wrap
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nn_trace_ctx"]

_naming = threading.local()


def _leak_check_mode() -> str:
    """MXNET_TRACER_CHECK: 'warn' (default) reports hybridize()-time
    tracer leaks as warnings, 'raise' makes them MXNetError, 'off'
    disables the scan."""
    from ..base import get_env
    mode = str(get_env("MXNET_TRACER_CHECK", "warn")).lower()
    return mode if mode in ("off", "warn", "raise") else "warn"


class _BlockScope:
    """ref: block.py _BlockScope — name management."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                if not hasattr(_naming, "counts"):
                    _naming.counts = {}
                count = _naming.counts.get(hint, 0)
                _naming.counts[hint] = count + 1
                prefix = f"{hint}{count}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


# trace context for mutable-state updates under jit (BatchNorm stats)
class _TraceCtx(threading.local):
    def __init__(self):
        self.active = False
        self.aux_updates: List[Tuple[Parameter, Any]] = []


_trace_ctx = _TraceCtx()
_NO_SCOPE = contextlib.nullcontext()


class nn_trace_ctx:
    def __enter__(self):
        self._saved = (_trace_ctx.active, _trace_ctx.aux_updates)
        _trace_ctx.active = True
        _trace_ctx.aux_updates = []
        return _trace_ctx

    def __exit__(self, *exc):
        _trace_ctx.active, _trace_ctx.aux_updates = self._saved


def record_aux_update(param: Parameter, new_value: NDArray):
    """Called by layers with mutable aux state (BatchNorm). Under a jit
    trace the update is routed out of the compiled function; eagerly it is
    applied immediately."""
    if _trace_ctx.active:
        _trace_ctx.aux_updates.append((param, new_value._data))
    else:
        param.data()._rebind(new_value._data)


def functional_call(block, pvals: Dict[str, Any], args, training=False,
                    rng_raw=None):
    """Run `block.forward(*args)` as a pure function of parameter values.

    The bridge between the stateful Gluon API and jax transforms: parameter
    buffers are temporarily rebound to the provided (possibly traced)
    values; mutable aux-state writes (BatchNorm stats) are captured and
    returned instead of applied. Used by hybridize (jit), the parallel
    train-step builders (pjit/shard_map), and checkpointing.

    Returns (outputs: tuple of jax values, aux_updates: {param_name: value}).
    """
    from ..ndarray.ndarray import NDArray as _ND, _wrap as _w
    plist = sorted(block._collect_params_with_prefix().items())
    saved = [(p, p._data._data if p._data is not None else None)
             for _, p in plist]
    call_args = [_w(a) if (hasattr(a, "shape") and hasattr(a, "dtype")
                           and not isinstance(a, _ND)) else a
                 for a in args]
    try:
        for (n, p) in plist:
            if p._data is not None and n in pvals:
                p._data._data = pvals[n]
        ctxs = []
        tc_scope = nn_trace_ctx()
        tc = tc_scope.__enter__()
        try:
            if rng_raw is not None:
                rng_scope = _random.trace_rng(
                    jax.random.wrap_key_data(rng_raw))
                rng_scope.__enter__()
            else:
                rng_scope = None
            try:
                with autograd._Scope(False, training):
                    out = block.forward(*call_args)
            finally:
                if rng_scope is not None:
                    rng_scope.__exit__(None, None, None)
            aux = {p.name: v for p, v in tc.aux_updates}
            # map back to prefixed names used in pvals
            # a shared Parameter answers to the first of its names
            name_of = {}
            for n, p in plist:
                name_of.setdefault(p.name, n)
            aux = {name_of.get(k, k): v for k, v in aux.items()}
        finally:
            tc_scope.__exit__(None, None, None)
    finally:
        for p, d in saved:
            if d is not None:
                p._data._data = d
    single = not isinstance(out, (list, tuple))
    outs = [out] if single else list(out)
    return tuple(o._data for o in outs), aux


class Block:
    """ref: block.py:131."""

    # the name this block is registered under in its parent: what
    # names it in a traced program's metadata (_traced_scope)
    _child_name: Optional[str] = None

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children: Dict[str, Block] = {}
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        """ref: block.py collect_params."""
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                value._child_name = name
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        name = name or str(len(self._children))
        self._children[name] = block
        block._child_name = name

    def _traced_scope(self):
        """While a parent's ``forward`` runs under a trace
        (``nn_trace_ctx``: a fused step, a hybridized block), the
        ``jax.named_scope`` of this block's name in its parent, so that
        an operation's path in the program's metadata reads
        ``forward/layers/0/attn/...``; no scope otherwise, and none for
        the root, which its caller names."""
        if _trace_ctx.active and self._child_name is not None:
            return jax.named_scope(self._child_name)
        return _NO_SCOPE

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from ..initializer import Uniform
        self.collect_params().initialize(init or Uniform(), ctx, verbose,
                                         force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        with self._traced_scope():
            out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary_rows = []

        def walk(block, depth):
            n_params = sum(int(onp.prod(p.shape or ()))
                           for p in block._reg_params.values())
            summary_rows.append(("  " * depth + block.name,
                                 block.__class__.__name__, n_params))
            for c in block._children.values():
                walk(c, depth + 1)

        walk(self, 0)
        print(f"{'Layer':<40}{'Type':<24}{'Params':<12}")
        print("-" * 76)
        for name, type_, n in summary_rows:
            print(f"{name:<40}{type_:<24}{n:<12}")

    # -- (de)serialization (ref: block.py:319 save_parameters) -----------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from ..ndarray import ndarray as nd_mod
        arg_dict = {key: val.data() for key, val in params.items()}
        nd_mod.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..ndarray import ndarray as nd_mod
        loaded = nd_mod.load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                assert name in loaded, \
                    f"Parameter '{name}' is missing in file '{filename}'"
        for name in loaded:
            if name not in params:
                assert ignore_extra, \
                    f"Parameter '{name}' loaded from file '{filename}' is " \
                    f"not present in Block"
                continue
            params[name].shape = loaded[name].shape
            if params[name]._data is None and params[name]._deferred_init:
                params[name]._finish_deferred_init()
            elif params[name]._data is None:
                params[name].initialize(ctx=ctx or current_context())
            params[name].set_data(loaded[name])

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    save_params = save_parameters
    load_params = load_parameters

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): " + repr(block).replace("\n", "\n  ")
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)


class HybridBlock(Block):
    """ref: block.py:705."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached = {}          # (shapes, dtypes, training) -> jitted fn
        self._flags = {}
        self._partition_if_dynamic = True

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None,
                  backward_bulk_size=None, **kwargs):
        """ref: block.py:537 — flags kept for parity; jax.jit implies
        static_alloc/static_shape."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape)
        self._cached = {}
        super().hybridize(active, **kwargs)

    def infer_shape(self, *args):
        self._infer_attrs("shape", *args)

    def _infer_attrs(self, attr, *args):
        """Run a shape-only trace so deferred params get concrete shapes."""
        params = {k: v for k, v in self._reg_params.items()}
        # deferred params are resolved inside forward via in_shape hooks
        # implemented per-layer (_infer_param_shapes)
        if hasattr(self, "_infer_param_shapes"):
            self._infer_param_shapes(*args)

    def cast(self, dtype):
        super().cast(dtype)
        self._cached = {}

    def __call__(self, *args):
        from ..symbol.symbol import Symbol
        if any(isinstance(a, Symbol) for a in args):
            # symbolic tracing (export): no jit cache, just compose the
            # graph (ref: block.py forward dispatches on input type)
            return self.forward(*args)
        if not self._active:
            return super().__call__(*args)
        with self._traced_scope():
            return self._call_cached(*args)

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """ref: block.py optimize_for — subgraph backend hook. On TPU the
        'backend' is always XLA via jit."""
        self.hybridize(True)
        return self(x, *args)

    # ------------------------------------------------------------------
    def _flat_params(self) -> List[Tuple[str, Parameter]]:
        out = []
        for name, p in sorted(self._collect_params_with_prefix().items()):
            out.append((name, p))
        return out

    def _call_cached(self, *args):
        """CachedOp analog (ref: cached_op.cc Forward :904)."""
        inputs = [a for a in args if isinstance(a, NDArray)]
        training = autograd.is_training()
        key = (tuple(tuple(i.shape) + (str(i.dtype),) for i in inputs),
               training)
        if self._cached.get(key, False) is None:
            # known dynamic-shape signature: skip the parameter gather
            # entirely and run eagerly
            return super(HybridBlock, self).__call__(*args)
        try:
            plist = self._flat_params()
            pvals = {n: p.data()._data for n, p in plist}
        except DeferredInitializationError:
            # first call resolves deferred shapes eagerly (ref:
            # block.py:786 _build_cache's deferred-infer)
            return super(HybridBlock, self).__call__(*args)
        if key not in self._cached:
            # recompile accounting (telemetry pillar 2): every cache
            # miss of the CachedOp analog is counted and classified
            # ("why did we recompile" — first compile vs shape/dtype/
            # train-flag change) with the triggering signature
            from ..telemetry import recompile as _recompile
            _recompile.record_recompile(
                f"{type(self).__name__}:{self.name}",
                _recompile.signature_of(inputs, training),
                kind="cached_op")
            try:
                self._cached[key] = self._build_jit(args, training)
            except (jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError,
                    jax.errors.TracerBoolConversionError,
                    jax.errors.TracerIntegerConversionError) as e:
                # dynamic-shape op in the graph (boolean_mask & co):
                # XLA needs static shapes, so this graph runs eagerly —
                # the analog of the reference's dynamic-shape executor
                # path that re-infers shapes every call
                # (graph_executor.cc:1421; test_dynamic_shape.py runs
                # boolean_mask under hybridize the same way). The jax
                # message is kept: data-dependent python control flow
                # raises the same error and the user must see which
                # line concretized a tracer.
                self._cached[key] = None
                # point at the user's line when their own Python consumed
                # the tracer (tracercheck pass); an all-internal traceback
                # means a dynamic-shape op, which is the expected case
                from ..passes.tracercheck import explain_concretization
                user_loc = explain_concretization(e)
                cause = (f"data-dependent python control flow at "
                         f"{user_loc} (a bug — hoist it out of forward)"
                         if user_loc else
                         "a dynamic-output-shape op (expected, e.g. "
                         "boolean_mask)")
                warnings.warn(
                    f"{type(self).__name__}: tracing failed; hybridize "
                    "falls back to eager execution for this input "
                    f"signature. Cause: {cause}. Trace error:\n{e}")
                return super(HybridBlock, self).__call__(*args)
        fn = self._cached[key]
        rng = jax.random.key_data(_random.next_key())
        in_vals = [i._data for i in inputs]
        outs_flat, aux_vals = fn(pvals, in_vals, rng)
        # write back mutated aux state (running stats)
        aux_params = self._cached_aux_params
        for p, v in zip(aux_params, aux_vals):
            p.data()._rebind(v)
        if autograd.is_recording():
            tape = autograd.current_tape()
            pnames = [n for n, _ in plist]
            np_ = len(pnames)

            def tape_fn(*arrays, _fn=fn, _rng=rng, _np=np_, _pn=tuple(pnames)):
                pv = dict(zip(_pn, arrays[:_np]))
                o, _ = _fn(pv, list(arrays[_np:]), _rng)
                return tuple(o)

            owners = [p.data() for _, p in plist] + list(inputs)
            in_arrays = [pvals[n] for n in pnames] + in_vals
            tape.record(tape_fn, in_arrays, list(outs_flat), owners)
        outs = [_wrap(o) for o in outs_flat]
        return outs[0] if self._cached_single else outs

    def _build_jit(self, sample_args, training):
        """Trace forward once into a jitted function."""
        block = self
        sample_inputs = [a for a in sample_args if isinstance(a, NDArray)]
        struct = [("nd", None) if isinstance(a, NDArray) else ("raw", a)
                  for a in sample_args]
        aux_params_found: List[Parameter] = []

        def pure_fn(pvals, in_vals, rng_raw):
            # rebind param buffers to traced values for the duration
            plist = block._flat_params()
            saved = [(p, p._data._data if p._data is not None else None)
                     for _, p in plist]
            args_it = iter(in_vals)
            call_args = []
            for kind, raw in struct:
                call_args.append(_wrap(next(args_it)) if kind == "nd" else raw)
            try:
                for (n, p) in plist:
                    if p._data is not None:
                        p._data._data = pvals[n]
                with nn_trace_ctx() as tc, \
                        _random.trace_rng(jax.random.wrap_key_data(rng_raw)), \
                        autograd._Scope(False, training):
                    out = block.forward(*call_args)
                aux_updates = list(tc.aux_updates)
            finally:
                for p, d in saved:
                    if d is not None:
                        p._data._data = d
            single = not isinstance(out, (list, tuple))
            outs = [out] if single else list(out)
            block._cached_single = single
            aux_params_found.clear()
            aux_params_found.extend(p for p, _ in aux_updates)
            return tuple(o._data for o in outs), tuple(
                v for _, v in aux_updates)

        jitted = jax.jit(pure_fn)
        # trigger trace now so _cached_single/_cached_aux_params are set
        rng = jax.random.key_data(_random.next_key())
        plist = self._flat_params()
        pvals = {n: p.data()._data for n, p in plist}
        jitted(pvals, [i._data for i in sample_inputs], rng)
        self._cached_aux_params = list(aux_params_found)
        # hybridize()-time tracer-leak check: a forward that stored an
        # intermediate on self just left a dead tracer behind; report it
        # NOW, naming the attribute, instead of the UnexpectedTracerError
        # jax raises wherever the attribute is next touched
        mode = _leak_check_mode()
        if mode != "off":
            from ..passes.tracercheck import scan_block_for_tracers
            leaks = scan_block_for_tracers(self)
            if leaks:
                msg = "; ".join(f.message for f in leaks[:3])
                if mode == "raise":
                    raise MXNetError(msg)
                warnings.warn(msg)
        return jitted

    def compile_signature(self, input_shapes, dtypes="float32",
                          training=False):
        """AOT compile-by-signature hook (mxserve warmup): populate the
        hybridize jit cache for ONE input signature using zero-filled
        inputs, without real data. ``input_shapes`` is one shape tuple
        or a list of them (full shapes, batch axis included); ``dtypes``
        a matching dtype or list. The compile is recorded by the
        recompile auditor as usual (classified ``first-compile`` during
        warmup) and later real traffic on the signature is a cache hit.

        Requires an active ``hybridize()`` — without it there is no jit
        cache to warm — and resolved parameter shapes (run one forward,
        or let deferred init resolve from the zeros here)."""
        if not self._active:
            raise MXNetError(
                f"{type(self).__name__}.compile_signature: call "
                "hybridize() first — eager blocks have no jit cache to "
                "warm")
        from ..ndarray.ndarray import zeros as nd_zeros
        shapes = [input_shapes] if input_shapes and \
            isinstance(input_shapes[0], int) else list(input_shapes)
        if isinstance(dtypes, str):
            dtypes = [dtypes] * len(shapes)
        args = [nd_zeros(tuple(s), dtype=d)
                for s, d in zip(shapes, dtypes)]
        with autograd._Scope(False, training):
            self(*args)
        return self

    def as_serving_engine(self, input_specs=None, **kwargs):
        """Export-to-engine path: wrap this block in a
        :class:`~mxnet_tpu.serve.engine.ServingEngine` (bucketed,
        batched, warmed inference — docs/serving.md). ``input_specs``
        are per-item shapes (no batch axis); remaining kwargs go to the
        engine (ladder, max_linger_ms, ...)."""
        from ..serve import ServingEngine
        return ServingEngine(self, input_specs=input_specs, **kwargs)

    def forward(self, x, *args):
        """ref: block.py:941 — dispatches hybrid_forward with F=nd for
        NDArray inputs, F=sym for Symbol inputs (the export trace)."""
        from ..symbol.symbol import Symbol
        if isinstance(x, Symbol):
            from .. import symbol as sym_ns
            from ..symbol.symbol import var as sym_var
            params = {name: sym_var(p.name)
                      for name, p in self._reg_params.items()}
            return self.hybrid_forward(sym_ns, x, *args, **params)
        from .. import ndarray as nd_ns
        params = {}
        for name, p in self._reg_params.items():
            try:
                params[name] = p.data()
            except DeferredInitializationError:
                self._deferred_infer_shape(x, *args)
                for p2 in self._reg_params.values():
                    p2._finish_deferred_init()
                params = {name: p.data()
                          for name, p in self._reg_params.items()}
                break
        return self.hybrid_forward(nd_ns, x, *args, **params)

    def _deferred_infer_shape(self, *args):
        if hasattr(self, "_infer_param_shapes"):
            self._infer_param_shapes(*args)
        else:
            raise MXNetError(
                f"Deferred initialization failed for {self.name}: layer "
                f"does not implement shape inference")

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0, remove_amp_cast=True):
        """ref: block.py:907 export — emits symbol JSON + params usable by
        SymbolBlock.imports / Module.load. Aux states (BN running
        stats) are saved under the aux: prefix, as the traced symbol
        classifies them — Module.load splits arg/aux by that prefix."""
        sym = self._trace_symbol()
        sym.save(f"{path}-symbol.json")
        aux_names = set(sym.list_auxiliary_states())
        params = self._collect_params_with_prefix()
        from ..ndarray import ndarray as nd_mod
        arg_dict = {}
        for name, p in params.items():
            kind = "aux" if p.name in aux_names else "arg"
            try:
                arg_dict[f"{kind}:{p.name}"] = p.data()
            except DeferredInitializationError as e:
                raise MXNetError(
                    "export requires resolved parameter shapes; run one "
                    "forward pass before export") from e
        nd_mod.save("%s-%04d.params" % (path, epoch), arg_dict)

    def _trace_symbol(self):
        """Trace hybrid_forward with Symbol proxies (ref: block.py
        _build_cache's symbol trace backing export). Single-"data"-input
        convention, like the reference's deployment flow; parameters
        must be initialized (run one forward first for deferred
        shapes)."""
        from ..symbol.symbol import var as sym_var
        return self.forward(sym_var("data"))


class SymbolBlock(HybridBlock):
    """ref: block.py:992 — wrap a Symbol + params as a Block."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        from ..symbol.symbol import Symbol, Group
        if isinstance(outputs, (list, tuple)):
            outputs = Group(outputs)
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._symbol = outputs
        self._input_names = [i.name for i in inputs]
        # graph variables carry their original fully-qualified names;
        # the block prefix must NOT be prepended or imports() misses
        # every parameter when matching loaded arrays by name
        self.params._prefix = ""
        arg_names = outputs.list_arguments()
        aux_names = set(outputs.list_auxiliary_states())
        for name in arg_names:
            if name not in self._input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            self.params.get(name, allow_deferred_init=True, grad_req="null")

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """ref: block.py:1025."""
        from ..symbol import symbol as sym_mod
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.Variable(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            from ..model import load_params
            arg_params, aux_params = load_params(
                param_file.rsplit("-", 1)[0],
                int(param_file.rsplit("-", 1)[1].split(".")[0]))
            for name, p in {**arg_params, **aux_params}.items():
                if name in ret.params:
                    ret.params[name].shape = p.shape
                    ret.params[name]._finish_deferred_init() \
                        if ret.params[name]._deferred_init else \
                        ret.params[name].initialize(ctx=ctx)
                    ret.params[name].set_data(p)
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        # SymbolBlock params are registered on the ParameterDict by
        # their graph names, not as _reg_params attributes; expose them
        # so save_parameters/load_parameters (and export) see them
        return {name: p for name, p in self.params.items()}

    def _trace_symbol(self):
        # the stored graph IS the symbol — re-export without re-tracing
        # (tracing through forward would need symbolic substitution)
        return self._symbol

    def forward(self, *args):
        from ..symbol.symbol import Symbol
        if any(isinstance(a, Symbol) for a in args):
            raise MXNetError(
                "composing an imported SymbolBlock into another "
                "symbolic trace is not supported; export from the "
                "original network (the SymbolBlock itself can "
                "export() — it re-emits its stored graph)")
        values = {}
        for name, a in zip(self._input_names, args):
            values[name] = a._data if isinstance(a, NDArray) else a
        for name, p in self.params.items():
            if p._data is None:
                # lazily infer from graph
                from ..symbol.symbol import _infer_all_shapes
                shapes = _infer_all_shapes(
                    self._symbol,
                    {n: tuple(v.shape) for n, v in values.items()})
                if shapes.get(name) is not None:
                    p.shape = shapes[name]
                    if p._deferred_init:
                        p._finish_deferred_init()
                    else:
                        p.initialize()
            values[name] = p.data()._data
        from ..symbol.symbol import eval_graph
        outs, aux = eval_graph(self._symbol, values,
                               autograd.is_training(), None)
        res = [_wrap(o) for o in outs]
        for name, v in aux.items():
            if name in self.params:
                self.params[name].data()._rebind(v)
        return res[0] if len(res) == 1 else res
