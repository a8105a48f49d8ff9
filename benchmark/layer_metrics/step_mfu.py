"""Step program: the FLOPs the model's forward and backward need a step
(from the configuration's shapes, two a multiply-add, nothing for
recomputation) over the step's period on the device's own clock (the
trace's steady window over its steps, ``trace_reduce.steady_window``)
times the chip's bf16 peak, in percent. The whole step's share of the
peak: it bounds every kernel's roofline beside it. The traced stretch
runs under the profiler, so where the host binds the step it reads a
little under what ``step_ms`` of an untraced run gives."""
from benchmark import peaks


def read(run):
    s = run.summary
    if not s or not s["steps"]:
        return None
    need = run.family.needed_flops(run.sizes, run.traffic)
    peak = peaks.lookup(run.peaks, run.device.device_kind)
    per_step_s = s["window_s"] / s["steps"]
    return 100.0 * need / (per_step_s * peak["bf16_flops"] * s["chips"])
