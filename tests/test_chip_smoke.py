"""chip_smoke.py and the rules it stands on, as far as a CPU can show
them: no size at which the full run carries on without a TPU, a
rehearsal that never calls itself a chip run, one compile-cache rule,
device contexts that raise instead of falling back, and a tuner that
records the candidate it could not run."""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import operator_tune

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "MXNET_COMPILE_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines()
            if ln.startswith("{")]


@pytest.mark.parametrize("args", [[], ["--multichip"]],
                         ids=["one-chip", "multichip"])
def test_full_size_smoke_refuses_to_run_without_a_tpu(args):
    proc = subprocess.run([sys.executable, SMOKE] + args, env=_env(),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_tiny_rehearsal_passes_and_names_the_platform_it_ran_on():
    proc = subprocess.run([sys.executable, SMOKE, "--tiny"], env=_env(),
                          capture_output=True, text=True, timeout=900)
    lines = _json_lines(proc.stdout)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert '"platform": "tpu"' not in proc.stdout
    # the contract's last line: these keys and nothing else
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert [p for p in ("resnet50", "bert", "serve2")
            if phases[p]["ok"]] == ["resnet50", "bert", "serve2"]
    # no JAX_COMPILATION_CACHE_DIR: the cache is at the fixed path
    # inside the checkout, and generated state starts inside it too
    assert phases["start"]["compile_cache_dir"] == \
        os.path.join(ROOT, ".jax_cache")
    for p in ("resnet50", "bert"):
        assert phases[p]["recompiles_after_warmup"] == 0
        assert phases[p]["losses"][-1] < phases[p]["losses"][0]
    assert phases["serve2"]["parity"]["greedy_exact_matches"] == \
        phases["serve2"]["new_tokens"]


@pytest.mark.parametrize("placed_outside", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR", "unset"])
def test_one_compile_cache_rule(tmp_path, placed_outside):
    """Placed from outside, no code sets jax_compilation_cache_dir —
    not the library flag at import, not the entry point's set-up;
    otherwise the entry point uses <repo>/.jax_cache."""
    outside = str(tmp_path / "outside")
    extra = {"MXNET_COMPILE_CACHE_DIR": str(tmp_path / "library_flag")}
    if placed_outside:
        extra["JAX_COMPILATION_CACHE_DIR"] = outside
    code = (
        "import sys, jax\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import mxnet_tpu, chip_smoke\n"
        "from mxnet_tpu.step.cache import enable_compile_cache\n"
        "print('IMPORT', jax.config.jax_compilation_cache_dir)\n"
        "enable_compile_cache(chip_smoke.CACHE_DIR)\n"
        "print('ENTRY', jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], env=_env(**extra),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-1500:]
    seen = dict(ln.split(" ", 1) for ln in proc.stdout.splitlines()
                if ln.startswith(("IMPORT ", "ENTRY ")))
    if placed_outside:
        assert seen == {"IMPORT": outside, "ENTRY": outside}
    else:
        assert seen == {"IMPORT": extra["MXNET_COMPILE_CACHE_DIR"],
                        "ENTRY": os.path.join(ROOT, ".jax_cache")}


@pytest.mark.parametrize("kind,device_id", [("tpu", 0), ("gpu", 0),
                                            ("tpu", 7)])
def test_accelerator_context_raises_without_the_chip(kind, device_id):
    """No fallback to the default platform, no clamping of the id."""
    with pytest.raises(mx.MXNetError, match="no fallback"):
        mx.Context(kind, device_id).jax_device()
    with pytest.raises(mx.MXNetError):
        mx.nd.zeros((2,), ctx=mx.Context(kind, device_id))


def test_operator_tune_records_a_failing_candidate(caplog, tmp_path,
                                                   monkeypatch):
    def broken(x):
        raise RuntimeError("Mosaic refused this kernel")

    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_HOME", str(tmp_path))  # an empty disk cache
    operator_tune.clear_cache()
    operator_tune.set_tuning_mode("auto")
    with caplog.at_level("WARNING", logger="mxnet_tpu.operator_tune"):
        label, _ = operator_tune.choose(
            "smoke_probe", [("kernel", broken), ("dense", lambda x: x + 1)],
            jnp.ones((4,)), key="smoke_probe|recorded")
    assert label == "dense"
    failed = {k: v for k, v in operator_tune.candidate_failures().items()
              if k.startswith("smoke_probe[kernel]|")}
    assert len(failed) == 1
    assert "Mosaic refused this kernel" in next(iter(failed.values()))
    assert any("Mosaic refused this kernel" in r.getMessage()
               for r in caplog.records)
    assert not any(k.startswith("smoke_probe[kernel]")
                   for k in operator_tune.cost_table())
    operator_tune.clear_cache()
