#!/usr/bin/env python3
"""benchmark/run.py: run one cell of BENCHMARK.json once, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1> [--tiny]

Everything that belongs to one cell is data the manifest names: the
configuration's file (``configs``), the traffic mix
(``benchmark/traffic/<traffic>.json``), the mix's driver loop
(``benchmark/traffic_kinds/<kind>.py``), the model family
(``benchmark/families/<family>.py``) and one reader a per-layer metric
(``benchmark/layer_metrics/<metric>.py``). Nothing of a cell lives in
this file, so a later PR adds cells and metrics by adding files and
manifest entries.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``:
every number that decided ``correct`` beside its limit. The same numbers
are the last lines of standard error.

Without a TPU (or with fewer chips than the cell asks for) it exits 2
and prints no result. ``--tiny`` is the rehearsal of the control flow at
the configuration's ``tiny`` sizes on whatever platform jax has; it
reports the platform it really ran on, and leaves out every metric that
only a device trace can give.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATE = os.path.join(ROOT, "benchmark", "_state")


def fail(code, msg):
    print(f"benchmark/run.py: {msg}", file=sys.stderr)
    sys.exit(code)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    fail(2, f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Run:
    """What a traffic kind and the per-layer readers are handed."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @contextlib.contextmanager
    def tracing(self):
        """Profile what runs inside; the trace is written under the
        cell's state directory, read, and removed."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        # the device's events and the harness's own spans, and nothing
        # of the Python tracer or the runtime's own spans: they slow a
        # step that the host binds, and nothing here reads them
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def read_trace(self):
        from benchmark import trace_reduce
        found = glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            return None
        raw = trace_reduce.read_xplane(found[0])
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return raw


def load_cell(workload, tiny):
    """``(manifest, cell, config, traffic)`` of one workload; with
    ``tiny`` the configuration's ``tiny`` block laid over its sizes, its
    traffic and its limits."""
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        fail(3, "the system under test (mxnet_tpu/) is not in this "
                "checkout")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    manifest = load_json("BENCHMARK.json")
    cell = by_name(manifest["workloads"], workload, "workload")
    config = load_json(by_name(manifest["configs"], cell["config"],
                               "config")["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if tiny:
        over = config["tiny"]
        config = {**config, **over["sizes"], "limits": over["limits"]}
        traffic = {**traffic, **over["traffic"]}
    return manifest, cell, config, traffic


def start_jax(cell, tiny):
    """Place the generated state, find the chip, switch the compile
    cache on; returns ``(device, devices, the cell's state directory)``.
    Without a TPU (and without ``tiny``), or with fewer chips than the
    cell asks for, the process ends here with code 2."""
    # generated state stays inside the checkout, at fixed paths: the
    # tuner's choices a cell, one compile cache for all
    cell_state = os.path.join(STATE, cell["name"] + ("-tiny" if tiny
                                                     else ""))
    os.makedirs(cell_state, exist_ok=True)
    os.environ["MXNET_HOME"] = cell_state
    # the cache is placed here, whatever the machine's environment says,
    # and without the machine's cap: one step program is larger than it
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu" and not tiny:
        fail(2, f"jax found no TPU (platform {device.platform!r}); a cell "
                "is measured on the chip or not at all (--tiny rehearses "
                "the control flow)")
    if len(devices) < cell["chips"]:
        fail(2, f"the cell needs {cell['chips']} chip(s), jax has "
                f"{len(devices)}")
    from mxnet_tpu.step.cache import enable_compile_cache
    enable_compile_cache(os.path.join(STATE, "jax_cache"),
                         min_compile_time_secs=0.0)
    return device, devices, cell_state


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's tiny sizes on whatever "
                         "platform jax has: a rehearsal, not a chip run")
    args = ap.parse_args(argv)

    manifest, cell, config, traffic = load_cell(args.workload, args.tiny)
    seconds = args.seconds if args.seconds is not None \
        else manifest["run_seconds"]
    device, devices, cell_state = start_jax(cell, args.tiny)

    from benchmark import trace_reduce
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    kind = importlib.import_module(
        f"benchmark.traffic_kinds.{traffic['kind']}")
    run = Run(cell=cell, sizes=config, traffic=traffic, family=family,
              opt=config["optimizer"], policy=config["dtype_policy"],
              limits=config["limits"], seed=args.seed, seconds=seconds,
              trace=bool(args.trace), tiny=args.tiny, device=device,
              t_start=T_START,
              trace_dir=os.path.join(cell_state, "trace"),
              peaks=load_json("benchmark", "peaks.json"))
    res = kind.run(run)

    metrics = {}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": {"platform": device.platform,
                      "kind": device.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": res["memory_peak_bytes"]}}
    if not args.trace:
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]) and m["name"] in res["end_to_end"]:
                metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    else:
        hlo = res["hlo_text"] or ""
        classes = trace_reduce.classify_hlo(hlo)
        raw = run.read_trace()
        summary = raw and trace_reduce.summarize(
            raw, trace_reduce.module_name(hlo), classes,
            res["annotations"], trace_reduce.hlo_flops(hlo))
        run.result, run.summary = res, summary
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary:
            out["device"]["busy_s"] = summary["busy_s"]
            out["device"]["window_s"] = summary["window_s"]
            out["breakdown"] = trace_reduce.breakdown(summary, classes)
            res["detail"]["trace"] = {
                "steps": summary["steps"],
                "module_runs": summary["module_runs"],
                "mxu_hlo_flops": summary["mxu_hlo_flops"],
                "host_window_s": res["window_s"],
                "host_steps": res["steps"]}
    out["detail"] = res["detail"]
    out["compared"] = res["compared"]
    for name, c in res["compared"].items():
        print(f"compared {name} value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
