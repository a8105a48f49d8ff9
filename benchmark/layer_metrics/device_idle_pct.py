"""Device: 1 - (union of the device-op intervals) / window, both from
the trace and on its clock (``trace_reduce.steady_window``), in
percent."""


def read(run):
    s = run.summary
    if not s or not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
