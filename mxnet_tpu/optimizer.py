"""Optimizers.

ref: python/mxnet/optimizer/optimizer.py (1,901 LoC) — registry of
Optimizer subclasses with create_state/update, lr/wd multipliers, and the
`Updater` wrapper used server-side by KVStore. The numeric updates delegate
to the fused update ops (ops/optimizer_ops.py ≙ src/operator/optimizer_op.cc)
so the whole step stays inside XLA.
"""
from __future__ import annotations

import math
import pickle
from typing import Dict, Optional

import numpy as onp

from .base import Registry, MXNetError
from .ndarray import ndarray as _nd
from .ndarray.ndarray import NDArray, invoke, zeros as nd_zeros
from .ops import optimizer_ops as oops

__all__ = ["Optimizer", "SGD", "Adam", "AdaGrad", "RMSProp", "AdaDelta",
           "Ftrl", "FTML", "NAG", "Signum", "SignSGD", "Adamax", "Nadam",
           "AdamW", "SGLD", "DCASGD", "LBSGD", "Test", "create", "register",
           "Updater", "get_updater"]

_REG = Registry("optimizer")


def register(klass):
    _REG.register(klass.__name__.lower())(klass)
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _REG.get(name.lower())(**kwargs)


class Optimizer:
    """ref: optimizer.py:48 Optimizer base — bookkeeping of per-index update
    counts, lr/wd multipliers, schedulers, rescale_grad/clip."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = param_dict or {}
        # width of the fused multi-tensor update (ref: the reference
        # optimizers read MXNET_OPTIMIZER_AGGREGATION_SIZE for the
        # multi_*_update kernels) — honored by the base update_multi
        # aggregation path for every optimizer with a fused_apply
        from .base import get_env
        self.aggregate_num = max(
            1, min(45, int(get_env("MXNET_OPTIMIZER_AGGREGATION_SIZE", 4))))

    # -- state ------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype == onp.float16:
            w32 = weight.astype("float32")
            return (w32, self.create_state(index, w32))
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == onp.float16:
            w32, base_state = state
            g32 = grad.astype("float32")
            self.update(index, w32, g32, base_state)
            weight._rebind(w32._data.astype(weight._data.dtype))
        else:
            self.update(index, weight, grad, state)

    # -- functional multi-tensor path (mxstep) ----------------------------
    @property
    def has_fused_apply(self) -> bool:
        """True when this optimizer provides a pure functional
        :meth:`fused_apply` — the fused train-step compiler
        (mxnet_tpu/step/) and the aggregated eager update both require
        it; optimizers without one downgrade to the per-param eager
        loop (the steplint pass flags them)."""
        return type(self).fused_apply is not Optimizer.fused_apply

    def fused_hyper(self, index):
        """Advance the update count for ``index`` and return the
        per-step scalar hyperparameters ``(lr, wd)`` with any per-step
        correction (Adam's bias correction) folded into ``lr`` — the
        exact host-side float64 arithmetic of the eager ``update``, so
        the fused path is bitwise-identical to it."""
        lr, wd, _ = self._common(index)
        return lr, wd

    def fused_signature(self):
        """The scalar hyperparameters :meth:`fused_apply` bakes into a
        trace as closure constants. Every jit cache built over
        fused_apply (the aggregated eager chunks, StepFunction's
        signature cache) keys on this tuple, so mutating one of these
        mid-training retraces instead of being silently ignored —
        lr/wd are NOT here (they travel as traced scalars).
        Subclasses extend with their own structural scalars."""
        return (float(self.rescale_grad),
                None if self.clip_gradient is None
                else float(self.clip_gradient))

    def fused_apply(self, indices, weights, grads, states, lrs, wds):
        """Pure multi-tensor update over raw jax arrays: returns
        ``(new_weights, new_states)`` lists without touching NDArrays —
        safe to call under a jit trace (the whole-train-step compiler)
        or eagerly (the aggregated update path). ``states`` entries are
        raw arrays / tuples of raw arrays / None, matching
        ``create_state``'s structure. ``lrs``/``wds`` are python floats
        (eager), weakly-typed f32 scalars (the aggregated eager chunks)
        or, under the fused step's trace, scalars of each weight's own
        dtype sliced out of the step's one ``(2, leaves)`` array
        (``step/stepfn.py _unpack_hyper``) — all promote exactly like
        the eager per-param kernels as long as a kernel uses them only
        as scalar x array of the weight's dtype."""
        raise NotImplementedError(
            f"{type(self).__name__} has no functional fused_apply; the "
            "fused step and aggregated update paths fall back to the "
            "eager per-param loop")

    def update_multi(self, indices, weights, grads, states):
        """Aggregated eager update: one fused multi-tensor kernel call
        per chunk of ``aggregate_num`` parameters
        (MXNET_OPTIMIZER_AGGREGATION_SIZE; ref: optimizer_op.cc
        multi_sgd_update and the list-form Updater path). Falls back to
        per-param updates when no ``fused_apply`` is available."""
        if not self.has_fused_apply:
            for i, w, g, s in zip(indices, weights, grads, states):
                self.update_multi_precision(i, w, g, s)
            return
        width = max(1, self.aggregate_num)
        for start in range(0, len(indices), width):
            idxs = list(indices[start:start + width])
            ws = list(weights[start:start + width])
            gs = list(grads[start:start + width])
            ss = list(states[start:start + width])
            hyper = [self.fused_hyper(i) for i in idxs]
            new_w, new_s = self._fused_eager_call(
                idxs, [w._data for w in ws], [g._data for g in gs],
                [_state_values(s) for s in ss],
                tuple(h[0] for h in hyper), tuple(h[1] for h in hyper))
            for w, nw in zip(ws, new_w):
                w._rebind(nw)
            for s, ns in zip(ss, new_s):
                _state_rebind(s, ns)

    def _fused_eager_call(self, idxs, w_raw, g_raw, s_raw, lrs, wds):
        """Dispatch one aggregated chunk through a cached jit: the
        eager aggregated path costs ONE XLA program per chunk, and —
        since the fused train step inlines the same expression DAG —
        matches both the per-param loop and the in-step apply bitwise.
        lrs/wds are traced scalars (schedulers don't retrace); the
        cache keys on the chunk's indices plus fused_signature() —
        every scalar the trace bakes in (rescale_grad, clip, momentum,
        betas, ...), so mid-run hyperparameter mutation retraces."""
        import jax
        key = (tuple(idxs),) + self.fused_signature()
        cache = self.__dict__.setdefault("_fused_jit_cache", {})
        fn = cache.get(key)
        if fn is None:
            frozen = tuple(idxs)

            def apply_chunk(ws, gs, ss, lrs, wds):
                return self.fused_apply(list(frozen), ws, gs, ss,
                                        list(lrs), list(wds))

            fn = cache[key] = jax.jit(apply_chunk)
        return fn(tuple(w_raw), tuple(g_raw), tuple(s_raw), lrs, wds)

    # -- hyperparams ------------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("LRScheduler of the optimizer has already been "
                             "defined")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def _common(self, index):
        self._update_count(index)
        return self._get_lr(index), self._get_wd(index), \
            (-1.0 if self.clip_gradient is None else self.clip_gradient)

    def __getstate__(self):
        d = self.__dict__.copy()
        d.pop("_fused_jit_cache", None)  # compiled callables don't pickle
        return d


def _assign(weight: NDArray, new: NDArray):
    weight._rebind(new._data)


_KERNEL_JITS: Dict = {}


def _jk(fn):
    """Jitted optimizer kernel for the eager per-param path: ONE
    compiled XLA program per update instead of one dispatch per jnp op.
    Per-step scalars (lr/wd/rescale_grad) stay traced — weak f32, so a
    scheduler changing lr never retraces — while structural scalars
    (momentum/betas/clip, which feed python arithmetic or control flow
    in the kernels) are static exactly like the fused step's closure
    captures. Because the fused train step (mxnet_tpu/step/) inlines
    the same expression DAG, eager and fused updates are
    bitwise-identical (XLA's FMA contraction applies equally to both)."""
    j = _KERNEL_JITS.get(fn)
    if j is None:
        import inspect
        import jax
        sig = inspect.signature(fn).parameters
        static = [n for n, p in sig.items()
                  if p.default is not inspect.Parameter.empty
                  and n not in ("lr", "wd", "rescale_grad")]
        j = _KERNEL_JITS[fn] = jax.jit(fn, static_argnames=static)
    return j


def _state_values(state):
    """Raw jax arrays of an optimizer state slot (None / NDArray /
    nested tuple of NDArrays) — the functional mirror of create_state's
    structure, consumed by fused_apply."""
    if state is None:
        return None
    if isinstance(state, (tuple, list)):
        return tuple(_state_values(s) for s in state)
    return state._data


def _state_rebind(state, new_values):
    """Write fused_apply's new raw arrays back into the stateful slot
    IN PLACE (the NDArray objects keep their identity — kvstore
    updaters, trainers, and checkpoints all hold references)."""
    if state is None:
        return
    if isinstance(state, (tuple, list)):
        for s, n in zip(state, new_values):
            _state_rebind(s, n)
    else:
        state._rebind(new_values)


def _rowsparse_parts(grad):
    """(row_indices int32, values, is_sparse) of a gradient. Sparse
    optimizer updates touch ONLY these rows (ref: the lazy/sparse update
    paths of src/operator/optimizer_op.cc, e.g. _sparse_adagrad_update
    and SGDUpdateRspImpl)."""
    from .ndarray.sparse import RowSparseNDArray
    if isinstance(grad, RowSparseNDArray):
        return (grad._aux["indices"].astype(_nd.jnp.int32),
                grad._aux["values"], True)
    return None, None, False


def _clip_scale(g, rescale, clip):
    g = g * rescale
    if clip is not None and clip >= 0:
        g = _nd.jnp.clip(g, -clip, clip)
    return g


def _rows_get(arr, idx):
    """(buffer, slots) for row reads on a dense or row_sparse array —
    row_sparse weights are updated on their compact payload, never via
    the dense view. Payload indices may be unsorted; a gradient row with
    no payload slot is an error (silently updating a wrong row would
    corrupt training)."""
    from .ndarray.sparse import RowSparseNDArray
    if isinstance(arr, RowSparseNDArray):
        own = arr._aux["indices"]
        order = _nd.jnp.argsort(own)
        sorted_idx = own[order]
        pos = _nd.jnp.clip(
            _nd.jnp.searchsorted(sorted_idx, idx.astype(own.dtype)),
            0, own.shape[0] - 1)
        if not bool((sorted_idx[pos] == idx.astype(own.dtype)).all()):
            raise MXNetError(
                "sparse update: gradient rows missing from the "
                "row_sparse weight/state payload")
        return arr._aux["values"], order[pos]
    return arr._data, idx


def _rows_set(arr, buf, slots, new_rows):
    from .ndarray.sparse import RowSparseNDArray
    if isinstance(arr, RowSparseNDArray):
        arr._aux["values"] = buf.at[slots].set(new_rows)
        arr._dense_cache = None
    else:
        arr._rebind(buf.at[slots].set(new_rows))


@register
class SGD(Optimizer):
    """ref: optimizer.py SGD → sgd_update/sgd_mom_update ops."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, weight.ctx, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        idx, gv, sparse = _rowsparse_parts(grad)
        if sparse and self.lazy_update:
            # lazy row-wise update: only rows present in the gradient are
            # touched — weights AND momentum (ref: SGDUpdateRspImpl /
            # sgd_mom lazy path, src/operator/optimizer_op.cc)
            lr, wd, clip = self._common(index)
            w, wslots = _rows_get(weight, idx)
            rows = w[wslots]
            g = _clip_scale(gv, self.rescale_grad, clip) + wd * rows
            if state is None:
                _rows_set(weight, w, wslots, rows - lr * g)
            else:
                m, mslots = _rows_get(state, idx)
                new_m = self.momentum * m[mslots] - lr * g
                _rows_set(weight, w, wslots, rows + new_m)
                _rows_set(state, m, mslots, new_m)
            return
        lr, wd, clip = self._common(index)
        if state is None:
            new_w = invoke(_jk(oops.sgd_update), [weight, grad], lr=lr, wd=wd,
                           rescale_grad=self.rescale_grad, clip_gradient=clip)
            _assign(weight, new_w)
        else:
            new_w, new_mom = invoke(_jk(oops.sgd_mom_update), [weight, grad, state],
                                    n_out=2, lr=lr, momentum=self.momentum,
                                    wd=wd, rescale_grad=self.rescale_grad,
                                    clip_gradient=clip)
            _assign(weight, new_w)
            _assign(state, new_mom)

    def update_multi_precision(self, index, weight, grad, state):
        """Dense fp16-weight updates take the fused mp_sgd kernels:
        master update + momentum + low-precision cast in ONE dispatch
        (and, on TPU, one Pallas kernel — the optimizer+cast fusion
        XLA won't do; mxnet_tpu/opt/kernels.py) instead of the base
        class's update-then-cast pair. Sparse grads keep the lazy
        row-wise path."""
        _idx, _gv, sparse = _rowsparse_parts(grad)
        if not (self.multi_precision and weight.dtype == onp.float16) \
                or sparse:
            return super().update_multi_precision(index, weight, grad,
                                                  state)
        w32, mom = state
        lr, wd, clip = self._common(index)
        if mom is None:
            new_w, new_w32 = invoke(
                _jk(oops.mp_sgd_update), [weight, grad, w32], n_out=2,
                lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                clip_gradient=clip)
        else:
            new_w, new_m, new_w32 = invoke(
                _jk(oops.mp_sgd_mom_update), [weight, grad, mom, w32],
                n_out=3, lr=lr, momentum=self.momentum, wd=wd,
                rescale_grad=self.rescale_grad, clip_gradient=clip)
            _assign(mom, new_m)
        _assign(weight, new_w)
        _assign(w32, new_w32)

    def fused_apply(self, indices, weights, grads, states, lrs, wds):
        """Functional multi-tensor SGD over raw arrays (ref:
        optimizer_op.cc multi_sgd_update / multi_sgd_mom_update) —
        the same sgd_update/sgd_mom_update kernels as the eager
        per-param path, so results are bitwise-identical to it."""
        clip = -1.0 if self.clip_gradient is None else self.clip_gradient
        new_w, new_s = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            if s is None:
                new_w.append(oops.sgd_update(
                    w, g, lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                    clip_gradient=clip))
                new_s.append(None)
            else:
                nw, nm = oops.sgd_mom_update(
                    w, g, s, lr=lr, momentum=self.momentum, wd=wd,
                    rescale_grad=self.rescale_grad, clip_gradient=clip)
                new_w.append(nw)
                new_s.append(nm)
        return new_w, new_s

    def fused_signature(self):
        return super().fused_signature() + (float(self.momentum),)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd_zeros(weight.shape, weight.ctx, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        if state is None:
            new_w = invoke(_jk(oops.sgd_update), [weight, grad], lr=lr, wd=wd,
                           rescale_grad=self.rescale_grad, clip_gradient=clip)
            _assign(weight, new_w)
        else:
            new_w, new_mom = invoke(_jk(oops.nag_mom_update), [weight, grad, state],
                                    n_out=2, lr=lr, momentum=self.momentum,
                                    wd=wd, rescale_grad=self.rescale_grad,
                                    clip_gradient=clip)
            _assign(weight, new_w)
            _assign(state, new_mom)

    def fused_apply(self, indices, weights, grads, states, lrs, wds):
        clip = -1.0 if self.clip_gradient is None else self.clip_gradient
        new_w, new_s = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            if s is None:
                new_w.append(oops.sgd_update(
                    w, g, lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                    clip_gradient=clip))
                new_s.append(None)
            else:
                nw, nm = oops.nag_mom_update(
                    w, g, s, lr=lr, momentum=self.momentum, wd=wd,
                    rescale_grad=self.rescale_grad, clip_gradient=clip)
                new_w.append(nw)
                new_s.append(nm)
        return new_w, new_s

    def fused_signature(self):
        return super().fused_signature() + (float(self.momentum),)


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                nd_zeros(weight.shape, weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        mean, var = state
        idx, gv, sparse = _rowsparse_parts(grad)
        if sparse and self.lazy_update:
            # lazy Adam: mean/var/weight rows not present in the gradient
            # are untouched (ref: adam_update lazy_update path,
            # src/operator/optimizer_op.cc AdamUpdateRspImpl)
            w, wslots = _rows_get(weight, idx)
            rows = w[wslots]
            g = _clip_scale(gv, self.rescale_grad, clip) + wd * rows
            mb, mslots = _rows_get(mean, idx)
            vb, vslots = _rows_get(var, idx)
            m_rows = self.beta1 * mb[mslots] + (1 - self.beta1) * g
            v_rows = self.beta2 * vb[vslots] + \
                (1 - self.beta2) * _nd.jnp.square(g)
            new_rows = rows - lr * m_rows / (_nd.jnp.sqrt(v_rows) +
                                             self.epsilon)
            _rows_set(weight, w, wslots, new_rows)
            _rows_set(mean, mb, mslots, m_rows)
            _rows_set(var, vb, vslots, v_rows)
            return
        new_w, new_mean, new_var = invoke(
            _jk(oops.adam_update), [weight, grad, mean, var], n_out=3, lr=lr,
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon, wd=wd,
            rescale_grad=self.rescale_grad, clip_gradient=clip)
        _assign(weight, new_w)
        _assign(mean, new_mean)
        _assign(var, new_var)

    def fused_hyper(self, index):
        # fold the bias correction into lr on the host in float64 —
        # the exact arithmetic of the eager update above
        lr, wd, _ = self._common(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        return lr * (math.sqrt(coef2) / coef1), wd

    def fused_apply(self, indices, weights, grads, states, lrs, wds):
        clip = -1.0 if self.clip_gradient is None else self.clip_gradient
        new_w, new_s = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            mean, var = s
            nw, nm, nv = oops.adam_update(
                w, g, mean, var, lr=lr, beta1=self.beta1, beta2=self.beta2,
                epsilon=self.epsilon, wd=wd, rescale_grad=self.rescale_grad,
                clip_gradient=clip)
            new_w.append(nw)
            new_s.append((nm, nv))
        return new_w, new_s

    def fused_signature(self):
        return super().fused_signature() + (
            float(self.beta1), float(self.beta2), float(self.epsilon))


@register
class AdamW(Optimizer):
    """ref: contrib adamw (_adamw_update, src/operator/contrib/adamw.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, eta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon, self.eta = beta1, beta2, epsilon, eta

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                nd_zeros(weight.shape, weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        mean, var = state
        new_w, new_mean, new_var = invoke(
            _jk(oops.adamw_update), [weight, grad, mean, var], n_out=3, lr=lr,
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon, wd=wd,
            eta=self.eta, rescale_grad=self.rescale_grad, clip_gradient=clip)
        _assign(weight, new_w)
        _assign(mean, new_mean)
        _assign(var, new_var)

    def fused_apply(self, indices, weights, grads, states, lrs, wds):
        clip = -1.0 if self.clip_gradient is None else self.clip_gradient
        new_w, new_s = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            mean, var = s
            nw, nm, nv = oops.adamw_update(
                w, g, mean, var, lr=lr, beta1=self.beta1, beta2=self.beta2,
                epsilon=self.epsilon, wd=wd, eta=self.eta,
                rescale_grad=self.rescale_grad, clip_gradient=clip)
            new_w.append(nw)
            new_s.append((nm, nv))
        return new_w, new_s

    def fused_signature(self):
        return super().fused_signature() + (
            float(self.beta1), float(self.beta2), float(self.epsilon),
            float(self.eta))


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return nd_zeros(weight.shape, weight.ctx, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        idx, gv, sparse = _rowsparse_parts(grad)
        if sparse:
            # _sparse_adagrad_update: history and weight rows not present
            # in the gradient are untouched (ref: optimizer_op.cc
            # _sparse_adagrad_update kernel)
            w, wslots = _rows_get(weight, idx)
            rows = w[wslots]
            g = _clip_scale(gv, self.rescale_grad, clip) + wd * rows
            h, hslots = _rows_get(state, idx)
            h_rows = h[hslots] + _nd.jnp.square(g)
            new_rows = rows - lr * g / (_nd.jnp.sqrt(h_rows) +
                                        self.float_stable_eps)
            _rows_set(weight, w, wslots, new_rows)
            _rows_set(state, h, hslots, h_rows)
            return
        new_w, new_h = invoke(oops.adagrad_update, [weight, grad, state],
                              n_out=2, lr=lr, epsilon=self.float_stable_eps,
                              wd=wd, rescale_grad=self.rescale_grad,
                              clip_gradient=clip)
        _assign(weight, new_w)
        _assign(state, new_h)


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        if self.centered:
            return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                    nd_zeros(weight.shape, weight.ctx, dtype=dt),
                    nd_zeros(weight.shape, weight.ctx, dtype=dt))
        return nd_zeros(weight.shape, weight.ctx, dtype=dt)

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        cw = -1.0 if self.clip_weights is None else self.clip_weights
        if self.centered:
            n, g_avg, delta = state
            new_w, new_n, new_g, new_d = invoke(
                _jk(oops.rmspropalex_update), [weight, grad, n, g_avg, delta],
                n_out=4, lr=lr, gamma1=self.gamma1, gamma2=self.gamma2,
                epsilon=self.epsilon, wd=wd, rescale_grad=self.rescale_grad,
                clip_gradient=clip, clip_weights=cw)
            _assign(weight, new_w); _assign(n, new_n)
            _assign(g_avg, new_g); _assign(delta, new_d)
        else:
            new_w, new_n = invoke(
                _jk(oops.rmsprop_update), [weight, grad, state], n_out=2, lr=lr,
                gamma1=self.gamma1, epsilon=self.epsilon, wd=wd,
                rescale_grad=self.rescale_grad, clip_gradient=clip,
                clip_weights=cw)
            _assign(weight, new_w); _assign(state, new_n)

    def fused_apply(self, indices, weights, grads, states, lrs, wds):
        clip = -1.0 if self.clip_gradient is None else self.clip_gradient
        cw = -1.0 if self.clip_weights is None else self.clip_weights
        new_w, new_s = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            if self.centered:
                n, g_avg, delta = s
                nw, nn, ng, nd = oops.rmspropalex_update(
                    w, g, n, g_avg, delta, lr=lr, gamma1=self.gamma1,
                    gamma2=self.gamma2, epsilon=self.epsilon, wd=wd,
                    rescale_grad=self.rescale_grad, clip_gradient=clip,
                    clip_weights=cw)
                new_w.append(nw)
                new_s.append((nn, ng, nd))
            else:
                nw, nn = oops.rmsprop_update(
                    w, g, s, lr=lr, gamma1=self.gamma1,
                    epsilon=self.epsilon, wd=wd,
                    rescale_grad=self.rescale_grad, clip_gradient=clip,
                    clip_weights=cw)
                new_w.append(nw)
                new_s.append(nn)
        return new_w, new_s

    def fused_signature(self):
        return super().fused_signature() + (
            float(self.gamma1), float(self.gamma2), float(self.epsilon),
            bool(self.centered),
            None if self.clip_weights is None else float(self.clip_weights))


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.9, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                nd_zeros(weight.shape, weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        acc_g, acc_d = state
        new_w, new_g, new_d = invoke(
            oops.adadelta_update, [weight, grad, acc_g, acc_d], n_out=3,
            rho=self.rho, epsilon=self.epsilon, wd=wd,
            rescale_grad=self.rescale_grad, clip_gradient=clip)
        _assign(weight, new_w); _assign(acc_g, new_g); _assign(acc_d, new_d)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                nd_zeros(weight.shape, weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        z, n = state
        new_w, new_z, new_n = invoke(
            oops.ftrl_update, [weight, grad, z, n], n_out=3, lr=lr,
            lamda1=self.lamda1, beta=self.beta, wd=wd,
            rescale_grad=self.rescale_grad, clip_gradient=clip)
        _assign(weight, new_w); _assign(z, new_z); _assign(n, new_n)


@register
class FTML(Optimizer):
    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return tuple(nd_zeros(weight.shape, weight.ctx, dtype=dt)
                     for _ in range(3))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        t = self._index_update_count[index]
        d, v, z = state
        new_w, new_d, new_v, new_z = invoke(
            oops.ftml_update, [weight, grad, d, v, z], n_out=4, lr=lr,
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon, wd=wd,
            rescale_grad=self.rescale_grad, clip_grad=clip, t=t)
        _assign(weight, new_w); _assign(d, new_d)
        _assign(v, new_v); _assign(z, new_z)


@register
class SignSGD(Optimizer):
    def __init__(self, learning_rate=0.01, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        new_w = invoke(oops.signsgd_update, [weight, grad], lr=lr, wd=wd,
                       rescale_grad=self.rescale_grad, clip_gradient=clip)
        _assign(weight, new_w)


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        return nd_zeros(weight.shape, weight.ctx, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        new_w, new_mom = invoke(oops.signum_update, [weight, grad, state],
                                n_out=2, lr=lr, momentum=self.momentum, wd=wd,
                                rescale_grad=self.rescale_grad,
                                clip_gradient=clip, wd_lh=self.wd_lh)
        _assign(weight, new_w); _assign(state, new_mom)


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                nd_zeros(weight.shape, weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        t = self._index_update_count[index]
        lr /= (1.0 - self.beta1 ** t)
        m, u = state
        g = grad * self.rescale_grad + wd * weight
        if clip >= 0:
            g = g.clip(-clip, clip)
        m_new = self.beta1 * m + (1.0 - self.beta1) * g
        u_new = _nd.invoke(
            lambda a, b: __import__("jax.numpy", fromlist=["maximum"]).maximum(a, b),
            [self.beta2 * u, g.abs()])
        _assign(m, m_new); _assign(u, u_new)
        _assign(weight, weight - lr * m_new / (u_new + 1e-8))


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        dt = str(weight.dtype)
        return (nd_zeros(weight.shape, weight.ctx, dtype=dt),
                nd_zeros(weight.shape, weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        t = self._index_update_count[index]
        g = grad * self.rescale_grad + wd * weight
        if clip >= 0:
            g = g.clip(-clip, clip)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 **
                                     ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m, v = state
        g_prime = g / (1.0 - self.m_schedule)
        m_new = self.beta1 * m + (1.0 - self.beta1) * g
        v_new = self.beta2 * v + (1.0 - self.beta2) * g * g
        m_prime = m_new / (1.0 - m_schedule_next)
        v_prime = v_new / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - momentum_t) * g_prime + momentum_t_1 * m_prime
        _assign(m, m_new); _assign(v, v_new)
        _assign(weight, weight - lr * m_bar / (v_prime.sqrt() + self.epsilon))


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (ref: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        g = grad * self.rescale_grad + wd * weight
        if clip >= 0:
            g = g.clip(-clip, clip)
        from . import random as _random
        noise = _random.normal(0, math.sqrt(lr), shape=weight.shape,
                               dtype=str(weight.dtype))
        _assign(weight, weight - lr / 2 * g + noise)


@register
class DCASGD(Optimizer):
    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = None if self.momentum == 0.0 else \
            nd_zeros(weight.shape, weight.ctx, dtype=str(weight.dtype))
        return (mom, weight.copy())

    def update(self, index, weight, grad, state):
        lr, wd, clip = self._common(index)
        g = grad * self.rescale_grad
        if clip >= 0:
            g = g.clip(-clip, clip)
        mom, prev = state
        comp = self.lamda * g * g * (weight - prev)
        if mom is not None:
            new_mom = self.momentum * mom - lr * (g + wd * weight + comp)
            _assign(mom, new_mom)
            step = new_mom
        else:
            step = -lr * (g + wd * weight + comp)
        _assign(prev, weight)
        _assign(weight, weight + step)


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layerwise scaling
    (ref: optimizer.py LBSGD)."""

    def __init__(self, warmup_strategy="linear", warmup_epochs=5,
                 batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(**kwargs)
        self.warmup_strategy = warmup_strategy


@register
class Test(Optimizer):
    """Mock optimizer for tests (ref: optimizer.py:1633)."""

    def create_state(self, index, weight):
        return nd_zeros(weight.shape, weight.ctx, dtype=str(weight.dtype))

    def update(self, index, weight, grad, state):
        _assign(weight, weight + grad * self.rescale_grad)
        _assign(state, grad)


class Updater:
    """ref: optimizer.py:1672 Updater — the callable KVStore servers run."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[int, object] = {}
        self.states_synced: Dict[int, bool] = {}
        self.aggregate_updates = optimizer.aggregate_num > 0

    def __call__(self, index, grad, weight):
        if isinstance(index, (list, tuple)):
            # aggregated call: one fused multi-tensor op per chunk
            # (ref: the list-form Updater path driving multi_sgd_update)
            for i, w in zip(index, weight):
                if i not in self.states:
                    self.states[i] = \
                        self.optimizer.create_state_multi_precision(i, w)
                    self.states_synced[i] = True
            # the fused path handles plain dense tensors only;
            # multi-precision states (w32, base) tuples and row_sparse
            # grads keep their scalar update semantics
            from .ndarray.sparse import RowSparseNDArray
            fusable = (self.aggregate_updates
                       and self.optimizer.has_fused_apply
                       and not self.optimizer.multi_precision
                       and not any(isinstance(g, RowSparseNDArray)
                                   for g in grad))
            if fusable:
                self.optimizer.update_multi(
                    list(index), list(weight), list(grad),
                    [self.states[i] for i in index])
            else:
                for i, g, w in zip(index, grad, weight):
                    self.optimizer.update_multi_precision(
                        i, w, g, self.states[i])
            return
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def set_states(self, states):
        """ref: optimizer.py Updater.set_states — the payload may be
        either the bare state dict or the (states, optimizer) pair that
        get_states(dump_optimizer=True) produces."""
        loaded = pickle.loads(states) if isinstance(states, bytes) \
            else states
        if isinstance(loaded, tuple) and len(loaded) == 2 and \
                isinstance(loaded[1], Optimizer):
            loaded, self.optimizer = loaded
            # keep the fused-update flag tracking the loaded optimizer
            self.aggregate_updates = \
                getattr(self.optimizer, "aggregate_num", 0) > 0
        self.states = loaded
        self.states_synced = {k: False for k in self.states}

    def get_states(self, dump_optimizer=False):
        return pickle.dumps((self.states, self.optimizer)
                            if dump_optimizer else self.states)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)


# opt registry by short alias (mirror reference names)
_REG.alias("sgd", "stochasticgradientdescent")
_REG.alias("adam", "adamoptimizer") if "adamoptimizer" not in _REG else None
