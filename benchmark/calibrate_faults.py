#!/usr/bin/env python3
"""benchmark/calibrate_faults.py: the faults a family plants in its own
reference, judged against a cell's limits, in one process on the chip.

    python3 benchmark/calibrate_faults.py --workload <cell>
        --seeds 101,102 [--faults decay_one,no_group_limit]
        [--out chiprun_out/calibrate] [--tiny]

Beside ``benchmark/calibrate.py`` (the program, the control one
precision down, half a batch), for the mistakes that only one family can
make: a family's reference that reads ``sizes["planted_fault"]`` lists
the names it knows in ``FAULTS`` (``ling_hybrid``: the decay left at 1,
the group limit left out of the routing, the rotary key left unrotated).
For every seed the sound reference follows the cell's three steps, then
the reference with each fault planted is put in the program's place and
set against it, as ``calibrate.py`` does with ``half_batch``.

That comparison sets the norms of gradients and changes side by side,
so a fault that leaves every distribution as it was slips by it. A
family may therefore bring ``block_witness(sizes, policy, weights,
batches, ctx)``: one block of the PROGRAM against the family's
reference on one input, tensor against tensor, as named numbers under
the configuration's ``witness_limits``. It is read once against the
sound reference (over a limit, the program itself is misjudged) and
once against each planted fault (over a limit, the fault is seen).

A line a seed says what each fault read and which numbers failed it;
the command exits 1 where a fault reads ``correct`` and no witness sees
it. Every reading is appended to ``<out>/<cell>.faults.jsonl``. A
benchmark run never runs this.
"""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=None,
                    help="comma-separated; the family's FAULTS by default")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "calibrate"))
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run as harness

    _, cell, config, traffic = harness.load_cell(args.workload, args.tiny)
    device, _, _ = harness.start_jax(cell, args.tiny)
    from benchmark import correctness
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    known = getattr(family, "FAULTS", ())
    faults = args.faults.split(",") if args.faults else list(known)
    if not faults or set(faults) - set(known):
        harness.fail(2, f"family {config['family']!r} plants {known}, "
                        f"not {faults}")
    os.makedirs(args.out, exist_ok=True)
    out_path = os.path.join(args.out, cell["name"]
                            + ("-tiny" if args.tiny else "")
                            + ".faults.jsonl")
    opt, policy = config["optimizer"], config["dtype_policy"]
    witness = getattr(family, "block_witness", None)
    witness_limits = config.get("witness_limits", {})
    import mxnet_tpu as mx
    ctx = mx.tpu(0) if device.platform == "tpu" else mx.cpu(0)
    misjudged = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = family.make_weights(config, policy, seed)
        batches = family.make_batches(config, policy, traffic, seed)
        keys = correctness.step_keys(seed)

        def follow(sizes):
            return correctness.reference_follow(
                family, sizes, opt, weights, batches, keys, "reference")

        def witnessed(sizes):
            """``(the witness's numbers, those over their limit)``."""
            if witness is None:
                return {}, []
            read = witness(sizes, policy, weights, batches, ctx)
            return read, [k for k, v in read.items()
                          if not v <= witness_limits[k]]

        ref = follow(config)
        line = {"cell": cell["name"], "seed": seed, "tiny": args.tiny,
                "platform": device.platform}
        read, over = witnessed(config)
        summary = {"program": {**read, "correct": not over, "failed": over}}
        if over:
            misjudged.append((seed, "program"))
        for fault in faults:
            planted = {**config, "planted_fault": fault}
            got = follow(planted)
            correct, compared, detail = correctness.compare(
                got, ref, config["limits"])
            read, over = witnessed(planted)
            line[fault] = got
            summary[fault] = {
                **{k: c["value"] for k, c in compared.items()},
                **detail["not_compared"], **read,
                "correct": correct and not over,
                "failed": [k for k, c in compared.items()
                           if not c["value"] <= c["limit"]] + over}
            if correct and not over:
                misjudged.append((seed, fault))
        line["summary"] = summary
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({"seed": seed, "platform": device.platform,
                          **summary}), flush=True)
        del weights, batches, ref, follow, witnessed
        gc.collect()
    print(json.dumps({"misjudged": misjudged}), flush=True)
    return 1 if misjudged else 0


if __name__ == "__main__":
    sys.exit(main())
