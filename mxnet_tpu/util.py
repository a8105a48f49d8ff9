"""Utility flags: numpy-semantics switches.

ref: python/mxnet/util.py:53-132 set_np_shape/is_np_array — the reference
gates NumPy-compatible shape/array semantics behind global flags so the
legacy 1-based API coexists with mx.np.
"""
from __future__ import annotations

import functools
import threading

_state = threading.local()


def _get(name, default=False):
    return getattr(_state, name, default)


def is_np_shape() -> bool:
    return _get("np_shape")


def set_np_shape(active: bool) -> bool:
    prev = is_np_shape()
    _state.np_shape = active
    return prev


def is_np_array() -> bool:
    return _get("np_array")


def set_np_array(active: bool) -> bool:
    prev = is_np_array()
    _state.np_array = active
    return prev


def set_np(shape=True, array=True):
    set_np_shape(shape)
    set_np_array(array)


def reset_np():
    set_np(False, False)


class _NumpyScope:
    def __init__(self, shape, array):
        self._shape, self._array = shape, array

    def __enter__(self):
        self._prev = (is_np_shape(), is_np_array())
        set_np(self._shape, self._array)

    def __exit__(self, *exc):
        set_np(*self._prev)


def np_shape(active=True):
    return _NumpyScope(active, is_np_array())


def np_array(active=True):
    return _NumpyScope(is_np_shape(), active)


def use_np(func):
    """Decorator form (ref: python/mxnet/util.py use_np)."""
    if isinstance(func, type):
        return func

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        with _NumpyScope(True, True):
            return func(*args, **kwargs)

    return wrapper


def get_gpu_count():
    from .context import num_gpus
    return num_gpus()


def d2h_fence(out):
    """Force a real device->host synchronization on `out` and return it.

    A timing fence that does not depend on `block_until_ready()`: a
    device-to-host transfer cannot return early — the scalar's bytes
    must exist on the host. Whether the two agree on the directly
    attached chip is printed by chip_smoke.py (one step timed both
    ways).
    Accepts NDArrays, jax arrays, or pytrees/sequences thereof; fetches
    one scalar from the first array leaf.
    """
    import jax
    import numpy as _onp
    empty = None
    # NDArrays are unregistered pytree types (hence leaves themselves,
    # wherever they sit in the structure); unwrap each to its jax array.
    for leaf in jax.tree.leaves(out):
        leaf = getattr(leaf, "_data", leaf)
        if not isinstance(leaf, jax.Array):
            continue  # host scalars/onp arrays need no device sync
        if leaf.size:
            # .ravel()[0] builds a FRESH sliced array each call, so the
            # transfer can never be served from a cached host copy
            _onp.asarray(leaf.ravel()[0])
            return out
        if empty is None:
            empty = leaf  # last resort if ALL array leaves are empty
    if empty is not None:
        _onp.asarray(empty)  # 0-byte fetch still joins definition
    return out


def d2h_fence_latency(out, reps: int = 3) -> float:
    """Median flat cost of d2h_fence on an ALREADY-COMPUTED buffer.

    The fence pays a fixed device-to-host round-trip; benchmark
    harnesses feed this to `net_time` so short regions aren't swamped
    by it.
    """
    import time as _time
    d2h_fence(out)  # ensure computed
    lats = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        d2h_fence(out)
        lats.append(_time.perf_counter() - t0)
    return sorted(lats)[len(lats) // 2]


def net_time(elapsed, lat):
    """Compute time of a fenced region, given the flat fence latency.

    The fetch request is dispatched while device compute is still
    running, so a long region's elapsed time includes only the RETURN
    half of the round trip; subtract lat/2, floored at 5% of elapsed so
    a jittery latency sample can never zero (or negate) the region.
    Callers should size the region so elapsed >> lat — check
    `lat_dominated(elapsed, lat)` and grow the iteration count or flag
    the result when it trips.
    """
    return max(elapsed - 0.5 * lat, 0.05 * elapsed)


def lat_dominated(elapsed, lat):
    """True when the fence round-trip is a material share (>30%) of the
    measured region — the corrected number is then noise-dominated and
    should be flagged or re-run with more iterations."""
    return elapsed <= 0 or (lat / elapsed) > 0.3
