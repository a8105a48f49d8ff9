"""Trainer host work: wall time less the thread's CPU time (``cpu_ns``)
of the program's ``train.step`` roots over the window's steps, in ms a
step: how long the thread inside ``step()`` did not run. Near 0 the
host computes through the step; near the device's busy time it waits
for the device. Source: the program's own span trees
(``program_span``)."""
from benchmark import span_reduce


def read(run):
    return span_reduce.blocked_ms_per_step(span_reduce.step_trees(run))
