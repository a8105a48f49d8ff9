"""Trainer host work: the programs that ran on the chip (the events of
its ``XLA Modules`` line) inside the trace's steady window over the
window's steps: the step's own program and whatever the host launched
between two steps (the key's split and its ``key_data``). A device
array made a leaf on the host path shows here as a program a leaf,
before it shows in ``step_ms``. Source: the device trace
(``trace_reduce.summarize``: ``module_runs`` over ``steps``)."""


def read(run):
    s = run.summary
    if not s or not s["steps"]:
        return None
    return s["module_runs"] / s["steps"]
