#!/usr/bin/env python3
"""benchmark/calibrate.py: the readings that a cell's limits are set
from, in one process on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 101,102,...
        [--control-seeds 3] [--out chiprun_out/calibrate] [--tiny]

For every seed: the program's readings off its own step object (as a
benchmark run takes them) against the reference's, which gives the lower
reading of each number. For the first ``--control-seeds`` seeds besides:
the control (the reference one precision down, the configuration's
``control_precision``) and the fault ``half_batch`` (the reference with
half of every batch left out), each put in the program's place and set
against the reference, which give the upper readings. A state left
unchanged reads 1 by the measure and needs no run. Every side is also
judged against the cell's own limits, as a benchmark run judges the
program: the line says which side read ``correct`` and which numbers
failed it, and the command exits 1 where a program's reading fails or a
control's or a fault's passes. Every reading is appended to
``<out>/<cell>.jsonl`` with its per-leaf norms, so that a number can be
judged without another run. A benchmark run never runs this.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--witness", default=None,
                    help="a further precision to run the reference in on "
                         "the control's seeds (a second witness for a gap "
                         "that looks too wide to be rounding)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "calibrate"))
    ap.add_argument("--no-program", action="store_true",
                    help="leave the program out: only the reference, its "
                         "control and its faults")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run as harness

    _, cell, config, traffic = harness.load_cell(args.workload, args.tiny)
    device, _, _ = harness.start_jax(cell, args.tiny)
    from benchmark import correctness
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    kind = importlib.import_module(
        f"benchmark.traffic_kinds.{traffic['kind']}")
    os.makedirs(args.out, exist_ok=True)
    out_path = os.path.join(args.out, cell["name"]
                            + ("-tiny" if args.tiny else "") + ".jsonl")
    opt, policy = config["optimizer"], config["dtype_policy"]
    seeds = [int(s) for s in args.seeds.split(",")]
    misjudged = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = harness.Run(cell=cell, sizes=config, traffic=traffic,
                          family=family, opt=opt, policy=policy, seed=seed,
                          device=device)
        sides = {}
        if args.no_program:
            batches = family.make_batches(config, policy, traffic, seed)
        else:
            net, trainer, fused, batches, readings = kind.prepare(run)
            sides["program"] = readings.readings()
            del net, trainer, fused, readings
            gc.collect()
        weights = family.make_weights(config, policy, seed)
        keys = correctness.step_keys(seed)

        def follow(precision, fault=None):
            return correctness.reference_follow(
                family, config, opt, weights, batches, keys, precision,
                fault)

        t1 = time.perf_counter()
        ref = follow("reference")
        t2 = time.perf_counter()
        if i < args.control_seeds:
            sides["control"] = follow(config["control_precision"])
            sides["half_batch"] = follow("reference", "half_batch")
            if args.witness:
                sides["witness_" + args.witness] = follow(args.witness)
        line = {"cell": cell["name"], "seed": seed, "tiny": args.tiny,
                "platform": device.platform, "reference": ref,
                "seconds": {"program": t1 - t0, "reference": t2 - t1}}
        summary = {}
        for side, r in sides.items():
            correct, compared, detail = correctness.compare(
                r, ref, config["limits"])
            line[side] = r
            summary[side] = {
                **{k: c["value"] for k, c in compared.items()},
                **detail["not_compared"], "correct": correct,
                "failed": [k for k, c in compared.items()
                           if not c["value"] <= c["limit"]],
                "leaves": [detail["grad_gap_leaf"],
                           detail["delta_gap_leaf"]]}
            # only the program has to pass; a witness is only looked at
            if side in ("program", "control", "half_batch") \
                    and correct != (side == "program"):
                misjudged.append((seed, side))
        line["summary"] = summary
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({"seed": seed, "platform": device.platform,
                          "seconds": line["seconds"], **summary}),
              flush=True)
        del weights, batches, ref, sides, follow
        gc.collect()
    print(json.dumps({"misjudged": misjudged}), flush=True)
    return 1 if misjudged else 0


if __name__ == "__main__":
    sys.exit(main())
