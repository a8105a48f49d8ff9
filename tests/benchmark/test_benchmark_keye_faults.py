"""``benchmark/calibrate_faults.py`` on the ``keye_vl2`` family's tiny
cell: the faults the family plants in its own reference, and its block
witness. A file of its own: the harness run in a process of its own is
most of a file's time under the suite's workers."""
import os

from bench_helpers import ROOT, run_harness

CELL = "keye_vl2_train_b1_t16384"


def test_the_faults_a_family_plants_fail_the_tiny_cell():
    """``benchmark/calibrate_faults.py`` on the tiny cell: the latest
    keys for the indexer's selection, the scores without their ReLU and
    the KL loss left out each read not correct against the sound
    reference, and the program's own attention block stands within its
    witness limits."""
    rc, last, err = run_harness(
        ["--workload", CELL, "--seeds", str(2 ** 31 + 43), "--tiny",
         "--out", os.path.join(ROOT, "benchmark", "_state", "calibrate")],
        script=os.path.join(ROOT, "benchmark", "calibrate_faults.py"))
    assert rc == 0 and last == {"misjudged": []}, err[-3000:]
