"""``benchmark/calibrate_faults.py`` on the ``nemotron_h`` family's tiny
cell: the faults the family plants in its own reference, and its block
witness. A file of its own: the harness run in a process of its own is
most of a file's time under the suite's workers."""
import os

from bench_helpers import ROOT, run_harness

CELL = "nemotron3_nano_train_b1_t8192"


def test_the_faults_a_family_plants_fail_the_tiny_cell():
    """``benchmark/calibrate_faults.py`` on the tiny cell: the decay left
    at 1, the skip left out, a head reading another group's B and C and
    the experts' square left out each read not correct against the sound
    reference, and the program's own mixer stands within its witness
    limit."""
    rc, last, err = run_harness(
        ["--workload", CELL, "--seeds", str(2 ** 31 + 41), "--tiny",
         "--out", os.path.join(ROOT, "benchmark", "_state", "calibrate")],
        script=os.path.join(ROOT, "benchmark", "calibrate_faults.py"))
    assert rc == 0 and last == {"misjudged": []}, err[-3000:]
