"""What has to come out as NOT correct does.

- The control: the reference put in the program's place, computed one
  precision below the configuration's (``control_precision``), at the
  tiny sizes on three seeds, fails at least one of the tiny limits while
  the reference set against itself passes all of them.
- A sound run of the harness at the tiny sizes (``--tiny`` skips only
  its look for a chip) reads ``correct`` true against the same limits and
  prints a well-formed last line; ``test_benchmark_faults.py`` breaks the
  timed path under it.
"""
import importlib

import pytest

from bench_helpers import manifest, run_harness

CELLS = [w["name"] for w in manifest()["workloads"]]


def tiny_case(cell_name):
    from benchmark import run as harness
    _, _, config, traffic = harness.load_cell(cell_name, tiny=True)
    return config, traffic, config["limits"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_reference_passes(cell):
    from benchmark import correctness
    config, traffic, limits = tiny_case(cell)
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    opt, policy = config["optimizer"], config["dtype_policy"]
    for seed in (21, 22, 2 ** 31 + 23):
        weights = family.make_weights(config, policy, seed)
        batches = family.make_batches(config, policy, traffic, seed)
        keys = correctness.step_keys(seed)
        ref = correctness.reference_follow(family, config, opt, weights,
                                           batches, keys, "reference")
        control = correctness.reference_follow(
            family, config, opt, weights, batches, keys,
            config["control_precision"])
        ok, compared, _ = correctness.compare(ref, ref, limits)
        assert ok and all(c["value"] == 0 for c in compared.values())
        ok, compared, _ = correctness.compare(control, ref, limits)
        assert not ok, (seed, compared)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_reads_correct(cell):
    rc, last, err = run_harness(
        ["--workload", cell, "--seed", str(2 ** 31 + 77), "--seconds",
         "0.3", "--trace", "0", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(last)
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"step_ms", "setup_s"} < set(last["metrics"])
    assert len(last["metrics"]) == 3
    for v in last["metrics"].values():
        assert v["value"] > 0 and " " not in v["unit"]
    assert list(last)[-1] == "compared"
    tail = [l for l in err.splitlines() if l.startswith("compared ")]
    assert len(tail) == len(last["compared"])
