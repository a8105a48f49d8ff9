"""The honest benchmark timing fence (util.d2h_fence and friends).

Every benchmark harness fences with a real device-to-host transfer —
the bytes must exist on the host — and not with block_until_ready()
alone. These tests pin the fence's edge-case contract that
the harnesses rely on (ref for the role: the engine sync points the
reference times against, include/mxnet/engine.h:230-236).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import nd
from mxnet_tpu.util import (d2h_fence, d2h_fence_latency, lat_dominated,
                            net_time)


def test_fence_returns_input_unchanged():
    x = jnp.arange(6.0)
    assert d2h_fence(x) is x
    lst = [jnp.ones((2, 2)), jnp.zeros(3)]
    assert d2h_fence(lst) is lst


def test_fence_handles_ndarray_top_level_and_nested():
    a = nd.array([1.0, 2.0])
    assert d2h_fence(a) is a
    nested = {"k": [a, nd.array([3.0])]}
    assert d2h_fence(nested) is nested


def test_fence_handles_host_scalars_mixed_with_arrays():
    # a python float first leaf must not short-circuit the array fence
    out = (3.0, jnp.ones((4,)))
    assert d2h_fence(out) is out


def test_fence_handles_empty_leaves_and_no_arrays(monkeypatch):
    d2h_fence(jnp.zeros((0, 3)))        # size-0 array: no IndexError
    d2h_fence([])                        # nothing to fence
    d2h_fence((1.0, "x", onp.ones(2)))   # host-only values

    # an empty FIRST leaf must not stop the real leaf being fetched
    fetched = []
    real_asarray = onp.asarray
    monkeypatch.setattr(
        onp, "asarray",
        lambda a, *k, **kw: (fetched.append(getattr(a, "size", None)),
                             real_asarray(a, *k, **kw))[1])
    d2h_fence([jnp.zeros((0,)), jnp.ones((2,))])
    assert fetched and fetched[-1] == 1  # one real scalar was pulled


def test_fence_latency_is_small_and_positive():
    x = jnp.ones((8, 8))
    lat = d2h_fence_latency(x)
    assert 0 <= lat < 5.0


def test_net_time_policy():
    # long region: subtract half the round trip
    assert net_time(10.0, 0.1) == pytest.approx(9.95)
    # jittery latency can never zero or negate a region
    assert net_time(0.05, 0.2) == pytest.approx(0.0025)
    assert net_time(0.0, 0.2) == 0.0


def test_lat_dominated_flag():
    assert not lat_dominated(3.0, 0.1)
    assert lat_dominated(0.2, 0.1)
    assert lat_dominated(0.0, 0.1)
