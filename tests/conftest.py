"""Test config: force CPU jax with a virtual 8-device mesh.

Mirrors the reference test strategy (SURVEY.md §4): CPU-runnable unit
tests; multi-device sharding validated on a virtual 8-device CPU mesh
(the analog of tools/launch.py local-mode multi-process tests).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "xla_cpu_experimental_ynn_fusion_type" not in flags:
    # the fused-step-equals-eager tests (test_step.py) are BITWISE: they
    # hold where a dot is compiled the same way inside one program and
    # alone. XLA:CPU of jax 0.9 hands dots inside a jitted program to
    # its YNN fusions and a single eager dot to Eigen, which differ by
    # 1 ulp; an empty fusion-type list switches those fusions off
    flags += " --xla_cpu_experimental_ynn_fusion_type="
os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402

# tests run on the CPU backend whatever the process environment says
jax.config.update("jax_platforms", "cpu")

import importlib.util  # noqa: E402

import numpy as onp  # noqa: E402
import pytest  # noqa: E402

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running example/convergence cases")


@pytest.fixture(autouse=True)
def _seed():
    """Seeded determinism (ref: tests/python/unittest/common.py:117
    @with_seed; MXNET_TEST_SEED/MXNET_MODULE_SEED env control)."""
    from mxnet_tpu import config
    seed = int(config.get("MXNET_TEST_SEED"))
    if seed < 0:
        seed = int(config.get("MXNET_MODULE_SEED"))
    if seed < 0:
        seed = 0
    onp.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    # tests/examples that call amp.init() must not leak the global cast
    # policy into later tests (bf16 casts silently loosen grad checks);
    # init() also mutates the op lists, so snapshot and restore them too
    from mxnet_tpu import amp as _amp
    _saved_target = set(_amp.TARGET_DTYPE_OPS)
    _saved_fp32 = set(_amp.FP32_OPS)
    yield
    _amp._STATE.active = False
    _amp._STATE.target_dtype = None
    _amp.TARGET_DTYPE_OPS.clear()
    _amp.TARGET_DTYPE_OPS.update(_saved_target)
    _amp.FP32_OPS.clear()
    _amp.FP32_OPS.update(_saved_fp32)


@pytest.fixture(scope="module")
def layer_gauges_cleaned():
    """A module whose nets run eager forwards leaves its expert layers'
    gauges behind under a layer's label (``moe_bias_changed_choice
    .layers.2``); a later module on the same worker that reads every
    gauge of a name (the benchmark's readers' tests) would find them.
    What the module registered under ``moe_`` goes when it ends."""
    from mxnet_tpu.telemetry import metrics
    before = set(metrics.all_metrics())
    yield
    for name in set(metrics.all_metrics()) - before:
        if name.startswith("moe_"):
            metrics.unregister(name)


@pytest.fixture
def load_example():
    """``load_example("gan/dcgan.py")`` imports one script of examples/
    as a module of its own (the test_examples*.py files call its
    ``main(argv)``)."""
    def load(relpath):
        path = os.path.join(EXAMPLES, relpath)
        name = "ex_" + os.path.basename(relpath)[:-3]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    return load


@pytest.fixture
def host_array_calls(monkeypatch):
    """``host_array_calls(fn)`` runs ``fn()`` and returns how often it
    called ``jnp.asarray`` / ``jnp.array`` / ``jax.device_put``: each a
    device array made on the host path (a program or a transfer of its
    own on the chip). The fused-step tests hold that count independent
    of the number of trainable leaves."""
    import jax.numpy as jnp

    def count(fn):
        calls = []
        with monkeypatch.context() as m:
            for mod, name in ((jnp, "asarray"), (jnp, "array"),
                              (jax, "device_put")):
                real = getattr(mod, name)
                m.setattr(mod, name, lambda *a, _r=real, _n=name, **k:
                          (calls.append(_n), _r(*a, **k))[1])
            fn()
        return len(calls)

    return count
