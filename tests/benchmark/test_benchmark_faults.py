"""The faults a one-chip training cell can have, planted under the
harness (``fault_driver.py``): a step that returns its state unchanged,
and half of the batch left out with the mean taken over the rest. The
rest of the run is the harness's own, minus its look for a chip
(``--tiny``); ``correct`` has to read false.
"""
import os

import pytest

from bench_helpers import ROOT, manifest, run_harness

DRIVER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fault_driver.py")
CELLS = [w["name"] for w in manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_under_the_harness_reads_not_correct(cell, fault):
    rc, last, err = run_harness(
        [ROOT, fault, "--workload", cell, "--seed", "31", "--seconds",
         "0.3", "--trace", "0", "--tiny"], script=DRIVER)
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is False, last["compared"]
    assert any(c["value"] > c["limit"] for c in last["compared"].values())
