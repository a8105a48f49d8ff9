"""Trainer host work: the sums of ``fused_step_host_seconds``,
``fused_step_dispatch_seconds`` and ``fused_step_writeback_seconds``
over the traced window, over its steps. Source: the program's own
timers (``program_span``)."""


def read(run):
    window = run.result["counters"]["window"]
    steps = run.result["steps"]
    if not steps or "host_seconds" not in window:
        return None
    return 1e3 * window["host_seconds"] / steps
