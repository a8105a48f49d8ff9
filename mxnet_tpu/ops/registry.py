"""Op registry: one registration mechanism for the whole op corpus.

TPU-native replacement for the reference's NNVM op registry
(ref: NNVM_REGISTER_OP, 354 uses in src/operator/**/*.cc, plus the legacy
MXNET_REGISTER_OP_PROPERTY path — SURVEY.md Appendix A). In the reference an
op carries FCompute/FInferShape/FGradient/... attributes; here an op is a
pure jax function (shape inference = jax.eval_shape, gradient = jax.vjp,
kernel = XLA fusion), so the registry only keeps name → (fn, metadata) for
the user-facing API codegen, aliases, and docs.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, List, Optional

from ..base import MXNetError

__all__ = ["register_op", "get_op", "list_ops", "OpInfo",
           "make_nd_function", "parse_bool_param"]


def parse_bool_param(v) -> bool:
    """Coerce an op param that may arrive as a string (symbol json /
    C-API attrs) to bool — the dmlc::Parameter bool-parsing role.

    Unknown strings raise MXNetError, as dmlc::Parameter does: the old
    fall-through to ``bool(str)`` silently read "off"/"no" (and any
    typo) as True."""
    if isinstance(v, str):
        s = v.strip().lower()
        if s in ("1", "true", "yes", "on"):
            return True
        if s in ("0", "false", "no", "off", ""):
            return False
        raise MXNetError(
            f"invalid boolean parameter value {v!r}: expected one of "
            f"1/true/yes/on or 0/false/no/off")
    return bool(v)


class OpInfo:
    __slots__ = ("name", "fn", "n_out", "differentiable", "arg_names",
                 "defaults", "needs_rng", "needs_train", "input_names",
                 "aux_updates", "visible_outputs")

    def __init__(self, name, fn, n_out, differentiable, needs_rng=False,
                 needs_train=False, input_names=None, aux_updates=None,
                 visible_outputs=None):
        self.name = name
        self.fn = fn
        self.n_out = n_out
        self.differentiable = differentiable
        self.needs_rng = needs_rng
        self.needs_train = needs_train
        # symbol-layer metadata (ref: nnvm FListInputNames /
        # FListAuxiliaryStates / FNumVisibleOutputs attrs):
        self.input_names = input_names    # declared tensor-input names
        # out_idx -> input_idx (aux var); may be callable(params) -> dict
        # for ops whose aux topology is instance-dependent (the graph
        # optimizer's _fused_group carries its aux map in node params,
        # mirroring how visible_outputs already supports callables)
        self.aux_updates = aux_updates or {}
        self.visible_outputs = visible_outputs  # user-visible output count
        sig = inspect.signature(fn)
        self.arg_names = []
        self.defaults = {}
        for pname, p in sig.parameters.items():
            if p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
                self.arg_names.append("*")
                continue
            self.arg_names.append(pname)
            if p.default is not p.empty:
                self.defaults[pname] = p.default

    def aux_updates_for(self, params) -> Dict[int, int]:
        """Resolve the aux-update map for a concrete node: static dict
        for ordinary ops, ``aux_updates(params)`` for param-dependent
        ones (e.g. the optimizer's fused groups)."""
        au = self.aux_updates
        if callable(au):
            au = au(params or {})
        return au or {}


_OPS: Dict[str, OpInfo] = {}


def register_op(name: str, n_out: int = 1, differentiable: bool = True,
                aliases: Optional[List[str]] = None, needs_rng: bool = False,
                needs_train: bool = False, input_names=None, aux_updates=None,
                visible_outputs=None, doc: Optional[str] = None):
    """Register a pure-jax op function under an MXNet-style name.

    The function's leading parameters without defaults are tensor inputs
    (jax arrays); keyword parameters with defaults are op params (the
    dmlc::Parameter analog). `needs_rng`: a threefry key is appended as a
    trailing tensor input by the nd wrapper. `needs_train`: the wrapper
    injects `_training=autograd.is_training()` (ref: the thread-local
    is_train_ flag, src/imperative/imperative.cc:26). `doc`: op docstring
    for lambda/loop-registered ops that cannot carry their own (the
    NNVM ``.describe(...)`` role); ignored when the fn already has one."""

    def deco(fn):
        if doc and not (fn.__doc__ or "").strip():
            fn.__doc__ = doc
        info = OpInfo(name, fn, n_out, differentiable, needs_rng, needs_train,
                      input_names, aux_updates, visible_outputs)
        _OPS[name] = info
        for a in aliases or []:
            _OPS[a] = info
        return fn

    return deco


def get_op(name: str) -> OpInfo:
    if name not in _OPS:
        raise MXNetError(f"operator '{name}' is not registered")
    return _OPS[name]


def has_op(name: str) -> bool:
    return name in _OPS


def list_ops() -> List[str]:
    return sorted(_OPS)


def make_nd_function(name: str) -> Callable:
    """Build the user-facing nd.<name> function: NDArray in/out, autograd
    recording (this is the codegen the reference does at import time —
    ref: python/mxnet/ndarray/register.py:116)."""
    info = _OPS[name]

    def nd_fn(*args, **kwargs):
        from ..ndarray.ndarray import NDArray, invoke, array as _arr

        out_kw = kwargs.pop("out", None)
        kwargs.pop("name", None)  # symbol-layer arg, ignored in eager
        inputs = []
        rest_params = {}
        param_names = [n for n in info.arg_names if n in info.defaults]
        pi = 0
        for a in args:
            if isinstance(a, NDArray):
                inputs.append(a)
            elif isinstance(a, (list, tuple)) and a and isinstance(a[0], NDArray):
                inputs.extend(a)
            else:
                # positional op-param after the tensor inputs
                while pi < len(param_names) and param_names[pi] in kwargs:
                    pi += 1
                if pi < len(param_names):
                    rest_params[param_names[pi]] = a
                    pi += 1
        # split kwargs into tensor inputs vs params by value type
        for k, v in kwargs.items():
            if isinstance(v, NDArray):
                inputs.append(v)
            else:
                rest_params[k] = v
        # FComputeEx dispatch: sparse storage types route to sparse
        # kernels when one exists (ref: imperative_utils.h:99 dispatch-
        # mode choice); otherwise fall through to the dense path
        from ..ndarray.sparse_ops import maybe_sparse_dispatch
        sparse_res = maybe_sparse_dispatch(name, inputs, rest_params)
        if sparse_res is not NotImplemented:
            if out_kw is not None:
                out_kw._rebind(sparse_res._data)
                return out_kw
            return sparse_res
        from .. import amp as _amp
        use_fn = info.fn
        _plan = _amp.cast_plan(name) if _amp.is_active() else None
        if _plan is not None:
            # cast INSIDE the recorded fn: swapping the input NDArrays
            # for cast copies would sever the parameter-owner chain and
            # silently drop gradients onto throwaway wrappers; in-fn
            # casting keeps owners intact and vjp routes the cotangent
            # back through astype to the fp32 master weights. The plan
            # is a policy SNAPSHOT so tape replay is dtype-stable even
            # if amp state changes before backward().
            def use_fn(*arrays, __f=info.fn, __p=_plan, **kw):
                return __f(*__p(list(arrays)), **kw)
            use_fn.__name__ = name  # profiler/fallback logs keep the op name
        n_out = rest_params.get("num_outputs", info.n_out) \
            if info.n_out == -1 else info.n_out
        if info.needs_train and "_training" not in rest_params:
            from .. import autograd as _ag
            rest_params["_training"] = _ag.is_training()
        if info.needs_rng:
            import jax as _jax
            from ..random import next_key
            from ..ndarray.ndarray import _wrap as _w
            # raw uint32 key data: vjp-safe (int cotangents are float0)
            inputs.append(_w(_jax.random.key_data(next_key())))
        # op-level tracing (telemetry pillar 1): when the profiler is
        # running, the op body executes under a TraceAnnotation so the
        # MXNet op name lands in XProf and the chrome-trace dump;
        # maybe_instrument is the identity when the profiler is off
        # (one branch on the hot path). Under an enclosing jit trace
        # (a fused step, a hybridized block) the op name goes into the
        # program's HLO metadata, profiler or not
        from ..telemetry.tracing import maybe_instrument as _instr
        from ..telemetry.tracing import trace_scope as _scope
        use_fn = _instr(name, use_fn)
        with _scope(name, [i._data for i in inputs]):
            out = invoke(use_fn, inputs, n_out=n_out,
                         differentiable=info.differentiable,
                         **rest_params)
        # Hide non-visible outputs in eager mode too (ref:
        # FNumVisibleOutputs applies to imperative invoke). Ops with
        # aux_updates are exempt: their hidden outputs are the new aux
        # values, which the eager caller (e.g. gluon BatchNorm) writes
        # back itself.
        vis = info.visible_outputs
        if callable(vis):  # param-dependent (e.g. Proposal output_score)
            vis = vis(rest_params)
        if vis is not None and not info.aux_updates \
                and isinstance(out, (tuple, list)) and vis < len(out):
            out = out[0] if vis == 1 else out[:vis]
        if out_kw is not None:
            out_kw._rebind(out._data if isinstance(out, NDArray) else out[0]._data)
            return out_kw
        return out

    nd_fn.__name__ = name
    nd_fn.__qualname__ = name
    nd_fn.__doc__ = info.fn.__doc__
    # marker for the dispatchlint pass: this is the instrumented registry
    # path (op tracing + sparse dispatch + autograd); a module-level
    # function shadowing a registered name lacks it and gets flagged
    nd_fn._mx_registry_dispatch = True
    return nd_fn
