"""Expanded MX* C ABI families driven from a pure-C consumer.

Covers the embeddable training surface beyond the predict subset:
NDArray slice/at/reshape/context, autograd record->backward->grad,
two-step symbol composition (CreateAtomicSymbol -> Compose) with
shape/type inference, KVStore init/push/pull, CSVIter iteration, and
the misc family (ref: include/mxnet/c_api.h — the ABI all reference
language bindings consume).
"""
import os
import site
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "mxnet_tpu", "native")


@pytest.mark.slow
def test_c_api_ext_families(tmp_path):
    from mxnet_tpu.native import build_capi
    build_capi()

    c_src = os.path.join(ROOT, "tests", "cpredict", "test_c_api_ext.c")
    c_bin = str(tmp_path / "test_c_api_ext")
    subprocess.run(["gcc", "-O2", c_src, f"-I{NATIVE}", f"-L{NATIVE}",
                    "-lmxtpu_capi", f"-Wl,-rpath,{NATIVE}", "-o", c_bin],
                   check=True, capture_output=True)

    env = dict(os.environ)
    # a hermetic PYTHONPATH for the embedded interpreter, on the CPU
    env["PYTHONPATH"] = ROOT + os.pathsep + site.getsitepackages()[0]
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([c_bin, str(tmp_path)], env=env,
                          cwd=str(tmp_path), capture_output=True,
                          text=True, timeout=380)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"C consumer failed:\n{out[-3000:]}"
    for flag in ("ndarray_ext_ok=1", "autograd_ok=1", "symbol_ok=1",
                 "kvstore_ok=1", "dataiter_ok=1", "misc_ok=1", "ALL_OK"):
        assert flag in out, f"missing {flag}:\n{out[-3000:]}"


@pytest.mark.slow
def test_c_api_training_example(tmp_path):
    """examples/c_api_training: full training loop through the ABI
    alone (symbol compose -> infer -> bind -> fwd/bwd -> sgd_update),
    asserting the loss falls — the capability every reference language
    binding derives from the C API."""
    from mxnet_tpu.native import build_capi
    build_capi()

    c_src = os.path.join(ROOT, "examples", "c_api_training",
                         "train_mlp.c")
    c_bin = str(tmp_path / "train_mlp")
    subprocess.run(["gcc", "-O2", c_src, f"-I{NATIVE}", f"-L{NATIVE}",
                    "-lmxtpu_capi", f"-Wl,-rpath,{NATIVE}", "-lm",
                    "-o", c_bin], check=True, capture_output=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + site.getsitepackages()[0]
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([c_bin], env=env, cwd=str(tmp_path),
                          capture_output=True, text=True, timeout=380)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"C training failed:\n{out[-3000:]}"
    assert "C_TRAIN_OK" in out, out[-2000:]
