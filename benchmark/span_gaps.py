#!/usr/bin/env python3
"""benchmark/span_gaps.py: one traced run of a cell, looked at through
the program's own spans.

    python3 benchmark/span_gaps.py --workload <cell> --seed <n>
                                   [--seconds <s>] [--tiny]

Runs ``benchmark/run.py --trace 1`` in this process (its result line is
printed as ever) and then prints one more JSON line, ``span_look``:

- ``idle_gaps``: the device's longest idle gaps in the steady window,
  each with the innermost span of the PROGRAM that covers its middle
  (``trace_reduce.idle_gaps`` given the program's span names beside the
  harness's two; the result line's ``breakdown.idle_gaps`` knows only
  the harness's until ``train_device_batches.ANNOTATIONS`` names the
  program's too);
- ``spans``: for every span name of the window's step trees, wall and
  CPU milliseconds a step and the attributes of the last one;
- ``phases``: device milliseconds a step by phase, ``unscoped`` the
  events whose HLO instruction names none, with the longest of those.

The harness hands the raw trace to nobody, so this wraps
``Run.read_trace`` to keep it; nothing of the run is changed.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark import span_reduce, trace_reduce  # noqa: E402


def gaps_by_span(raw, run, trees, top=5):
    """``[[span, seconds], ...]`` of the first chip's steady window,
    the spans being the harness's annotations and every name of the
    program's step trees."""
    program = trace_reduce.module_name(run.result["hlo_text"] or "")
    names = set(run.result["annotations"])
    for root, below in trees or ():
        names.update(s["name"] for s in [root] + below)
    host = [h for h in raw["host"] if h[0] in names]
    for dev in raw["devices"].values():
        window = trace_reduce.steady_window(dev["modules"], program)
        if window is None:
            continue
        start, end, _ = window
        ops = [e for e in dev["ops"] if start <= e[1] < end]
        return [[name, ns * 1e-9] for name, ns in
                trace_reduce.idle_gaps(ops, host, end, top)]
    return None


def span_table(trees):
    """name -> wall and CPU ms a step over the trees, roots included."""
    if not trees:
        return None
    table = {}
    for root, below in trees:
        for s in [root] + below:
            row = table.setdefault(s["name"], {"wall_ms": 0.0,
                                               "cpu_ms": 0.0})
            row["wall_ms"] += 1e-3 * s["dur_us"] / len(trees)
            row["cpu_ms"] += 1e-6 * s["attrs"].get("cpu_ns", 0) \
                / len(trees)
            row["attrs"] = {k: v for k, v in s["attrs"].items()
                            if k != "cpu_ns"}
    return table


def phase_table(run, top=8):
    by_phase = span_reduce.phase_seconds(run)
    if by_phase is None:
        return None
    steps = run.summary["steps"]
    phases = {name: span_reduce.phase_of(path) for name, path in
              span_reduce.run_paths(run).items()}
    loose = [(name, sec) for name, sec in run.summary["op_seconds"].items()
             if phases.get(name) is None]
    return {"fwd_ms": 1e3 * by_phase["fwd"] / steps,
            "bwd_ms": 1e3 * by_phase["bwd"] / steps,
            "opt_ms": 1e3 * by_phase["opt"] / steps,
            "unscoped_ms": 1e3 * by_phase[None] / steps,
            "busy_ms": 1e3 * run.summary["busy_s"] / steps,
            "unscoped_top": [[n, 1e3 * s / steps] for n, s in loose[:top]]}


def main(argv=None):
    kept = {}
    read_trace = harness.Run.read_trace

    def keeping(self):
        kept["run"], kept["raw"] = self, read_trace(self)
        return kept["raw"]

    harness.Run.read_trace = keeping
    failure = None
    try:
        harness.main(list(argv if argv is not None else sys.argv[1:])
                     + ["--trace", "1"])
    except RuntimeError as e:  # a reader refused the run: still look
        failure = str(e)
    finally:
        harness.Run.read_trace = read_trace
    run, raw = kept.get("run"), kept.get("raw")
    look = {"failure": failure}
    if run is not None:
        trees = span_reduce.step_trees(run)
        look["spans"] = span_table(trees)
        if raw and run.summary:
            look["idle_gaps"] = gaps_by_span(raw, run, trees)
            look["phases"] = phase_table(run)
    print(json.dumps({"span_look": look}), flush=True)
    return 1 if failure else 0


if __name__ == "__main__":
    sys.exit(main())
