"""Symbol: the symbolic graph API.

TPU-native re-design of the reference symbolic layer (ref: nnvm::Symbol /
nnvm::Graph consumed per SURVEY.md Appendix B; python/mxnet/symbol/symbol.py
— Symbol class :3,321 LoC with simple_bind :1499 / bind :1763). In the
reference, binding runs graph passes (MXGradient, MXPlanMemory, shape/type
inference — src/executor/graph_executor.cc:388) and attaches engine ops.
Here a Symbol is a lightweight Python DAG whose bind compiles to ONE
jax.jit-compiled function — gradient construction is jax.vjp, memory
planning/fusion/bulking are XLA's job (SURVEY.md §3.3 "TPU mapping").
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError
from ..context import Context, current_context
from ..ops.registry import get_op, has_op, list_ops, OpInfo

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "zeros", "ones"]



def _auto_name(op_name: str) -> str:
    """Auto names come from the active NameManager (ref: name.py
    NameManager/Prefix; symbol.py _set_name)."""
    from ..name import NameManager
    base = op_name.lower().lstrip("_")
    return NameManager.current().get(None, base)


class _Node:
    """Graph node (ref: nnvm::Node — op + NodeAttrs + input entries)."""

    __slots__ = ("op", "name", "inputs", "params", "attrs", "_n_out")

    def __init__(self, op: Optional[str], name: str,
                 inputs: List[Tuple["_Node", int]], params: dict,
                 attrs: Optional[dict] = None):
        self.op = op                  # None for variables
        self.name = name
        self.inputs = inputs          # list of (node, out_index)
        self.params = params
        self.attrs = attrs or {}
        if op is None:
            self._n_out = 1
        else:
            info = get_op(op)
            n_out = info.n_out
            if n_out == -1:
                n_out = int(params.get("num_outputs", 1))
            self._n_out = n_out

    @property
    def is_variable(self):
        return self.op is None

    @property
    def info(self) -> Optional[OpInfo]:
        return get_op(self.op) if self.op else None


class Symbol:
    """A set of output entries over the node DAG."""

    def __init__(self, outputs: List[Tuple[_Node, int]]):
        self._outputs = list(outputs)

    # ------------------------------------------------------------------
    # graph introspection
    # ------------------------------------------------------------------
    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    def _topo_nodes(self) -> List[_Node]:
        seen = {}
        order: List[_Node] = []

        def visit(node):
            if id(node) in seen:
                return
            seen[id(node)] = node
            for inp, _ in node.inputs:
                visit(inp)
            order.append(node)

        for node, _ in self._outputs:
            visit(node)
        return order

    def list_arguments(self) -> List[str]:
        """Variable names in topo order, aux excluded (ref: symbol.py
        list_arguments)."""
        out = []
        aux = set(self.list_auxiliary_states())
        for n in self._topo_nodes():
            if n.is_variable and n.name not in aux:
                out.append(n.name)
        return out

    def list_auxiliary_states(self) -> List[str]:
        """Aux vars = variable inputs consumed at an op's aux positions
        (ref: FListAuxiliaryStates, e.g. BatchNorm moving stats)."""
        aux = []
        for n in self._topo_nodes():
            if n.op is None:
                continue
            info = n.info
            au = info.aux_updates_for(n.params)
            if not au:
                continue
            aux_positions = set(au.values())
            for pos, (inp, _) in enumerate(n.inputs):
                if pos in aux_positions and inp.is_variable \
                        and inp.name not in aux:
                    aux.append(inp.name)
        return aux

    def list_outputs(self) -> List[str]:
        names = []
        for node, idx in self._outputs:
            suffix = "output" if node._n_out == 1 or True else ""
            names.append(f"{node.name}_{suffix}" if idx == 0
                         else f"{node.name}_output{idx}")
        return names

    def list_inputs(self):
        return [n.name for n in self._topo_nodes() if n.is_variable]

    def get_internals(self) -> "Symbol":
        entries = []
        for n in self._topo_nodes():
            for i in range(n._n_out):
                entries.append((n, i))
        return Symbol(entries)

    def get_children(self) -> Optional["Symbol"]:
        node = self._outputs[0][0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    def __getitem__(self, index):
        if isinstance(index, str):
            for i, name in enumerate(self.list_outputs()):
                if name == index or name.rsplit("_", 1)[0] == index:
                    return Symbol([self._outputs[i]])
            raise MXNetError(f"no output named {index}")
        if isinstance(index, slice):
            return Symbol(self._outputs[index])
        return Symbol([self._outputs[index]])

    def __len__(self):
        return len(self._outputs)

    def __iter__(self):
        for i in range(len(self._outputs)):
            yield self[i]

    @property
    def num_outputs(self):
        return len(self._outputs)

    def __repr__(self):
        return f"<Symbol {self.name or 'group'}>"

    # -- attributes (ref: symbol.py attr/attr_dict) ---------------------
    def attr(self, key):
        return self._outputs[0][0].attrs.get(key)

    def attr_dict(self):
        return {n.name: dict(n.attrs) for n in self._topo_nodes() if n.attrs}

    def _set_attr(self, **kwargs):
        self._outputs[0][0].attrs.update(kwargs)

    # ------------------------------------------------------------------
    # composition & arithmetic
    # ------------------------------------------------------------------
    def _entry(self) -> Tuple[_Node, int]:
        if len(self._outputs) != 1:
            raise MXNetError("operation on grouped symbol is not supported")
        return self._outputs[0]

    def __call__(self, *args, **kwargs):
        """Compose: replace free variables (ref: symbol composition)."""
        raise MXNetError("symbol composition via __call__ is not supported; "
                         "pass inputs at construction")

    def _binary(self, other, op_name, scalar_op=None, reverse=False):
        if isinstance(other, Symbol):
            ins = [other._entry(), self._entry()] if reverse \
                else [self._entry(), other._entry()]
            return _make_node(op_name, ins, {})
        s = float(other)
        return _make_node(scalar_op, [self._entry()], {"scalar": s})

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        if isinstance(o, Symbol):
            return o.__sub__(self)
        return _make_node("_rminus_scalar", [self._entry()],
                          {"scalar": float(o)})

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        if isinstance(o, Symbol):
            return o.__truediv__(self)
        return _make_node("_rdiv_scalar", [self._entry()],
                          {"scalar": float(o)})

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __neg__(self):
        return _make_node("_mul_scalar", [self._entry()], {"scalar": -1.0})

    def __eq__(self, o):
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal",
                            "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal",
                            "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # method-style ops mirroring NDArray methods
    def reshape(self, shape, **kw):
        return _make_node("reshape", [self._entry()], {"shape": tuple(shape)})

    def transpose(self, axes=None):
        return _make_node("transpose", [self._entry()],
                          {"axes": tuple(axes) if axes else None})

    def sum(self, axis=None, keepdims=False):
        return _make_node("sum", [self._entry()],
                          {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return _make_node("mean", [self._entry()],
                          {"axis": axis, "keepdims": keepdims})

    def flatten(self):
        return _make_node("Flatten", [self._entry()], {})

    def astype(self, dtype):
        return _make_node("cast", [self._entry()], {"dtype": str(dtype)})

    def slice_axis(self, axis, begin, end):
        return _make_node("slice_axis", [self._entry()],
                          {"axis": axis, "begin": begin, "end": end})

    # ------------------------------------------------------------------
    # shape/type inference (ref: infer_graph_attr_pass.cc:649/679 — here
    # jax.eval_shape over the compiled graph function)
    # ------------------------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        """Full inference: contradictory input shapes RAISE (ref:
        infer_graph_attr_pass.cc fixed-point errors); underdetermined
        entries come back as None."""
        return self._infer_shape_impl(False, *args, _strict=True,
                                      **kwargs)

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, _strict=False, **kwargs):
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        known: Dict[str, tuple] = {}
        if args:
            for name, shape in zip(arg_names, args):
                if shape is not None:
                    known[name] = tuple(shape)
        known.update({k: tuple(v) for k, v in kwargs.items()})
        shapes = _infer_all_shapes(self, known, strict=_strict)
        arg_shapes = [shapes.get(n) for n in arg_names]
        aux_shapes = [shapes.get(n) for n in aux_names]
        out_shapes = [shapes.get(("__out__", i))
                      for i in range(len(self._outputs))]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """Dtype propagation (ref: infer_graph_attr_pass.cc:679
        InferType): unknown parameter variables adopt their node's
        carrier dtype (result_type of known inputs — e.g. fc_weight
        becomes float64 when data is), `dtype`-parameterized ops
        (cast/amp_cast/creation) set their own output type."""
        arg_names = self.list_arguments()
        known: Dict[str, object] = {}
        if args:
            for name, t in zip(arg_names, args):
                if t is not None:
                    known[name] = onp.dtype(t)
        known.update({k: onp.dtype(v) for k, v in kwargs.items()})
        types = _infer_all_types(self, known)
        arg_types = [types.get(n, onp.dtype(onp.float32))
                     for n in arg_names]
        aux_types = [types.get(n, onp.dtype(onp.float32))
                     for n in self.list_auxiliary_states()]
        out_types = []
        for node, oi in self._outputs:
            if node.is_variable:
                out_types.append(types.get(node.name,
                                           onp.dtype(onp.float32)))
            else:
                out_types.append(types.get((id(node), oi),
                                           onp.dtype(onp.float32)))
        return arg_types, out_types, aux_types

    # ------------------------------------------------------------------
    # binding (ref: symbol.py:1499 simple_bind → graph_executor.cc:1913)
    # ------------------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    stype_dict=None, group2ctx=None, shared_arg_names=None,
                    shared_exec=None, shared_buffer=None, **kwargs):
        from ..executor import Executor
        ctx = ctx or current_context()
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        shapes = _infer_all_shapes(
            self, {k: tuple(v) for k, v in kwargs.items()})
        from ..ndarray.ndarray import zeros as nd_zeros
        type_dict = type_dict or {}
        args = {}
        for n in arg_names:
            if shapes.get(n) is None:
                raise MXNetError(f"cannot infer shape for argument {n}; "
                                 f"pass it to simple_bind")
            args[n] = nd_zeros(shapes[n], ctx,
                               dtype=onp.dtype(type_dict.get(n, "float32")).name)
        auxs = {n: nd_zeros(shapes[n], ctx) for n in aux_names}
        if isinstance(grad_req, str):
            grad_reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, dict):
            grad_reqs = {n: grad_req.get(n, "null") for n in arg_names}
        else:
            grad_reqs = dict(zip(arg_names, grad_req))
        grads = {n: nd_zeros(shapes[n], ctx) for n in arg_names
                 if grad_reqs[n] != "null"}
        return Executor(self, ctx, args, grads, grad_reqs, auxs)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from ..executor import Executor
        ctx = ctx or current_context()
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        args_grad = args_grad or {}
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        aux_states = aux_states or {}
        if isinstance(grad_req, str):
            grad_reqs = {n: (grad_req if n in args_grad or grad_req == "null"
                             else "null") for n in arg_names}
            if grad_req != "null" and not args_grad:
                grad_reqs = {n: "null" for n in arg_names}
        elif isinstance(grad_req, dict):
            grad_reqs = {n: grad_req.get(n, "null") for n in arg_names}
        else:
            grad_reqs = dict(zip(arg_names, grad_req))
        # ensure missing aux get allocated
        from ..ndarray.ndarray import zeros as nd_zeros
        if aux_names and not aux_states:
            shapes = _infer_all_shapes(
                self, {n: a.shape for n, a in args.items()})
            aux_states = {n: nd_zeros(shapes[n], ctx) for n in aux_names}
        # MXNET_SUBGRAPH_BACKEND: partition with the named property
        # before compilation (ref: env_var.md:319; build_subgraph.cc)
        from ..base import get_env
        backend = get_env("MXNET_SUBGRAPH_BACKEND", "")
        bind_sym = self
        if backend:
            from ..subgraph import build_subgraph
            bind_sym = build_subgraph(self, property_name=backend)
        return Executor(bind_sym, ctx, dict(args), dict(args_grad),
                        grad_reqs, dict(aux_states))

    # evaluation helper used by tests: symbol.eval(ctx, **bindings)
    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx or current_context(), kwargs, grad_req="null")
        return ex.forward()

    # ------------------------------------------------------------------
    # gradient symbol (ref: symbol.py gradient via MXGradient pass): not a
    # graph transform here — Executor.backward uses jax.vjp directly.
    # ------------------------------------------------------------------

    # ------------------------------------------------------------------
    # serialization (ref: nnvm::Graph JSON; symbol.py tojson/load)
    # ------------------------------------------------------------------
    def tojson(self) -> str:
        nodes = self._topo_nodes()
        idx = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jnodes.append({
                "op": n.op or "null",
                "name": n.name,
                "attrs": {k: repr(v) for k, v in n.params.items()}
                if n.params else {},
                "inputs": [[idx[id(i)], oi, 0] for i, oi in n.inputs],
            })
        return json.dumps({
            "nodes": jnodes,
            "arg_nodes": [i for i, n in enumerate(nodes) if n.is_variable],
            "node_row_ptr": list(range(len(nodes) + 1)),
            "heads": [[idx[id(n)], oi, 0] for n, oi in self._outputs],
            "attrs": {"mxnet_version": ["int", 10600]},
        }, indent=2)

    def save(self, fname: str):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # shape helper used by visualization
    def _infer_node_shapes(self, shape_dict):
        return {}


def _parse_attr_value(v: str):
    try:
        return eval(v, {"__builtins__": {}}, {})  # values were repr()'d
    except Exception:
        return v


def load_json(json_str: str) -> Symbol:
    data = json.loads(json_str)
    nodes: List[_Node] = []
    for jn in data["nodes"]:
        params = {k: _parse_attr_value(v)
                  for k, v in (jn.get("attrs") or {}).items()}
        inputs = [(nodes[i], oi) for i, oi, *_ in jn["inputs"]]
        op = None if jn["op"] == "null" else jn["op"]
        nodes.append(_Node(op, jn["name"], inputs, params))
    heads = [(nodes[i], oi) for i, oi, *_ in data["heads"]]
    return Symbol(heads)


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


# ---------------------------------------------------------------------------
# construction API
# ---------------------------------------------------------------------------

def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, stype=None, **kwargs):
    """ref: symbol.py var/Variable."""
    attrs = dict(attr or {})
    if shape is not None:
        attrs["__shape__"] = tuple(shape)
    if lr_mult is not None:
        attrs["__lr_mult__"] = lr_mult
    if wd_mult is not None:
        attrs["__wd_mult__"] = wd_mult
    if dtype is not None:
        attrs["__dtype__"] = str(dtype)
    if init is not None:
        attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    attrs.update(kwargs)
    from ..attribute import AttrScope
    attrs = AttrScope.current().get(attrs)
    return Symbol([(_Node(None, name, [], {}, attrs), 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    entries = []
    for s in symbols:
        entries.extend(s._outputs)
    return Symbol(entries)


def zeros(shape, dtype="float32", **kw):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return _make_node("_sym_zeros", [], {"shape": shape, "dtype": dtype})


def ones(shape, dtype="float32", **kw):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return _make_node("_sym_ones", [], {"shape": shape, "dtype": dtype})


def _make_node(op_name: str, inputs: List[Tuple[_Node, int]], params: dict,
               name: Optional[str] = None, attrs: Optional[dict] = None
               ) -> Symbol:
    info = get_op(op_name)
    name = name or _auto_name(op_name)
    # merge scope attrs (ref: attribute.py AttrScope applied by the
    # symbol creators; explicit attrs win)
    from ..attribute import AttrScope
    attrs = AttrScope.current().get(attrs)
    # auto-create variables for missing declared inputs (ref: the reference
    # auto-creates fullyconnected0_weight etc. at compose time)
    if info.input_names:
        expected = list(info.input_names)
        if params.get("no_bias") and "bias" in expected:
            expected.remove("bias")
        while len(inputs) < len(expected):
            vname = f"{name}_{expected[len(inputs)]}"
            inputs = list(inputs) + [(_Node(None, vname, [], {}), 0)]
    node = _Node(op_name, name, list(inputs), params, attrs)
    n_out = node._n_out
    info_vis = info.visible_outputs
    if callable(info_vis):  # param-dependent (e.g. Proposal output_score)
        info_vis = info_vis(params)
    vis = info_vis if info_vis is not None else n_out
    return Symbol([(node, i) for i in range(vis)])


def make_symbol_function(op_name: str):
    """Codegen for sym.<op> (ref: symbol/register.py generated functions)."""
    info = get_op(op_name)

    def sym_fn(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        inputs: List[Tuple[_Node, int]] = []
        params = {}
        param_names = [n for n in info.arg_names if n in info.defaults]
        pi = 0
        for a in args:
            if isinstance(a, Symbol):
                inputs.append(a._entry())
            elif isinstance(a, (list, tuple)) and a and isinstance(a[0], Symbol):
                inputs.extend(x._entry() for x in a)
            else:
                while pi < len(param_names) and param_names[pi] in kwargs:
                    pi += 1
                if pi < len(param_names):
                    params[param_names[pi]] = a
                    pi += 1
        # keyword tensor inputs must respect declared order
        kw_syms = {k: v for k, v in kwargs.items() if isinstance(v, Symbol)}
        if kw_syms:
            order = info.input_names or list(kw_syms)
            for k in order:
                if k in kw_syms:
                    inputs.append(kw_syms[k]._entry())
            for k in kw_syms:
                if info.input_names and k not in info.input_names:
                    inputs.append(kw_syms[k]._entry())
        for k, v in kwargs.items():
            if not isinstance(v, Symbol):
                params[k] = v
        return _make_node(op_name, inputs, params, name=name,
                          attrs=dict(attr) if attr else None)

    sym_fn.__name__ = op_name
    sym_fn.__doc__ = info.fn.__doc__
    return sym_fn


# ---------------------------------------------------------------------------
# graph evaluation (shared with Executor)
# ---------------------------------------------------------------------------

def eval_graph(symbol: Symbol, value_map: Dict[str, "jax.Array"],
               training: bool, rng_raw):
    """Evaluate the DAG as one pure jax computation. Under jax.jit this is
    traced once — the whole reference executor machinery (memory planning,
    bulking, engine push — graph_executor.cc:1016,1288,1384) becomes XLA's
    problem. Returns (outputs, aux_update_dict)."""
    from .. import random as _random
    from ..telemetry import tracing as _tracing

    values: Dict[Tuple[int, int], object] = {}
    aux_updates: Dict[str, object] = {}
    # symbolic-domain op tracing (telemetry pillar 1): under jit this
    # trace runs ONCE, and trace_scope stamps each node's op name into
    # the compiled HLO permanently, profiler or not; trace_ops is False
    # when the profiler is off and the loop below pays no op span
    trace_ops = _tracing.active("symbolic")

    def run():
        for node in symbol._topo_nodes():
            if node.is_variable:
                if node.name not in value_map:
                    raise MXNetError(f"unbound variable {node.name}")
                values[(id(node), 0)] = value_map[node.name]
                continue
            info = node.info
            ins = [values[(id(i), oi)] for i, oi in node.inputs]
            params = dict(node.params)
            params.pop("num_args", None)
            if info.needs_train:
                params["_training"] = training
            if info.needs_rng:
                ins.append(jax.random.key_data(_random.next_key()))
            with _tracing.trace_scope(info.name, ins):
                if trace_ops:
                    with _tracing.op_span(info.name, "symbolic",
                                          node=node.name):
                        out = info.fn(*ins, **params)
                else:
                    out = info.fn(*ins, **params)
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            for i, o in enumerate(outs):
                values[(id(node), i)] = o
            for out_idx, in_idx in info.aux_updates_for(node.params).items():
                src, _ = node.inputs[in_idx]
                if src.is_variable:
                    aux_updates[src.name] = outs[out_idx]

    if rng_raw is not None:
        with _random.trace_rng(jax.random.wrap_key_data(rng_raw)):
            run()
    else:
        run()
    outputs = [values[(id(n), oi)] for n, oi in symbol._outputs]
    return outputs, aux_updates


def _infer_all_types(symbol: Symbol, known: Dict[str, object]
                     ) -> Dict[object, object]:
    """Rule-based dtype propagation over the traced graph (the InferType
    pass role). Per node: output dtype = its `dtype` param when present
    (cast/creation family), else result_type of the known input dtypes;
    unknown *variable* inputs (auto-created weights/biases) are
    backfilled with that carrier dtype, mirroring the reference's
    bidirectional fixed-point for the common layer case."""
    types: Dict[object, object] = dict(known)
    for node in symbol._topo_nodes():
        if node.is_variable:
            continue
        in_types = []
        for inode, oi in node.inputs:
            t = types.get(inode.name) if inode.is_variable \
                else types.get((id(inode), oi))
            in_types.append(t)
        ks = [t for t in in_types if t is not None]
        carrier = onp.result_type(*ks) if ks else onp.dtype(onp.float32)
        for (inode, _), t in zip(node.inputs, in_types):
            if t is None and inode.is_variable:
                types[inode.name] = carrier
        dt = node.params.get("dtype")
        out_t = onp.dtype(dt) if dt is not None else carrier
        for i in range(node._n_out if node._n_out and node._n_out > 0
                       else 1):
            types[(id(node), i)] = out_t
    return types


def _infer_all_shapes(symbol: Symbol, known: Dict[str, tuple],
                      strict: bool = False) -> Dict[object, tuple]:
    """Shape inference via jax.eval_shape (abstract evaluation — zero FLOPs).

    Forward-only: variables without known shapes must be inferable from
    op semantics; for the auto-created parameter variables of NN layers we
    solve their shapes from the op's param struct (ref: the per-op
    FInferShape functions, e.g. fully_connected.cc FullyConnectedShape)."""
    shapes: Dict[object, tuple] = dict(known)
    nodes = symbol._topo_nodes()
    for n in nodes:
        if n.is_variable and n.name not in shapes:
            hint = n.attrs.get("__shape__")
            if hint:
                shapes[n.name] = tuple(hint)

    def entry_shape(entry):
        node, oi = entry
        if node.is_variable:
            return shapes.get(node.name)
        return shapes.get((id(node), oi))

    for node in nodes:
        if node.is_variable:
            continue
        info = node.info
        in_shapes = [entry_shape(e) for e in node.inputs]
        # solve parameter-variable shapes from op semantics
        _solve_param_shapes(node, in_shapes, shapes)
        in_shapes = [entry_shape(e) for e in node.inputs]
        if any(s is None for s in in_shapes):
            continue
        try:
            specs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in in_shapes]
            params = dict(node.params)
            params.pop("num_args", None)
            if info.needs_train:
                params["_training"] = False
            if info.needs_rng:
                specs.append(jax.ShapeDtypeStruct((2,), jnp.uint32))
            out = jax.eval_shape(lambda *a: info.fn(*a, **params), *specs)
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            for i, o in enumerate(outs):
                shapes[(id(node), i)] = tuple(o.shape)
        except Exception as e:
            if strict:
                # all inputs known yet abstract eval failed: the given
                # shapes are CONTRADICTORY — surface it (ref: InferShape
                # fixed-point errors), don't return an all-None triple
                raise MXNetError(
                    f"shape inference failed at op '{node.op}' "
                    f"(node '{node.name}') with input shapes "
                    f"{in_shapes}: {e}") from e
            continue
    for i, e in enumerate(symbol._outputs):
        shapes[("__out__", i)] = entry_shape(e)
    return shapes


def _solve_param_shapes(node: _Node, in_shapes, shapes):
    """Infer auto-created weight/bias/gamma shapes from data shape + params
    (the FInferShape role for the common NN layers)."""
    op = node.op
    p = node.params
    data_shape = in_shapes[0] if in_shapes else None
    if data_shape is None:
        return

    def setvar(pos, shape):
        if pos < len(node.inputs):
            var_node, _ = node.inputs[pos]
            if var_node.is_variable and shapes.get(var_node.name) is None:
                shapes[var_node.name] = tuple(int(x) for x in shape)

    if op == "FullyConnected":
        nh = int(p.get("num_hidden"))
        flat_in = data_shape[1] if len(data_shape) == 2 or not p.get(
            "flatten", True) else int(onp.prod(data_shape[1:]))
        if p.get("flatten", True) is False:
            flat_in = data_shape[-1]
        setvar(1, (nh, flat_in))
        setvar(2, (nh,))
    elif op in ("Convolution", "Convolution_v1"):
        nf = int(p.get("num_filter"))
        kern = tuple(p.get("kernel"))
        ng = int(p.get("num_group", 1))
        setvar(1, (nf, data_shape[1] // ng) + kern)
        setvar(2, (nf,))
    elif op == "Deconvolution":
        nf = int(p.get("num_filter"))
        kern = tuple(p.get("kernel"))
        ng = int(p.get("num_group", 1))
        setvar(1, (data_shape[1], nf // ng) + kern)
        setvar(2, (nf,))
    elif op in ("BatchNorm", "BatchNorm_v1", "_contrib_SyncBatchNorm"):
        axis = int(p.get("axis", 1))
        c = data_shape[axis]
        for pos in (1, 2, 3, 4):
            setvar(pos, (c,))
    elif op in ("LayerNorm",):
        axis = int(p.get("axis", -1))
        c = data_shape[axis]
        setvar(1, (c,))
        setvar(2, (c,))
    elif op in ("GroupNorm", "InstanceNorm"):
        c = data_shape[1]
        setvar(1, (c,))
        setvar(2, (c,))
    elif op in ("SoftmaxOutput", "Softmax"):
        if p.get("multi_output"):
            setvar(1, (data_shape[0],) + tuple(data_shape[2:]))
        else:
            setvar(1, data_shape[:-1])
    elif op in ("LinearRegressionOutput", "LogisticRegressionOutput",
                "MAERegressionOutput", "SVMOutput"):
        setvar(1, data_shape)
    elif op == "Embedding":
        setvar(1, (int(p.get("input_dim")), int(p.get("output_dim"))))
    elif op == "LeakyReLU" and p.get("act_type") == "prelu":
        setvar(1, (data_shape[1],))
