"""Device time under a nested scope of the step program: a block's
attribute name and, somewhere below it, a ``jax.named_scope`` inside
that block (``("attn", "window")``, ``("moe", "experts")``). Beside
``span_reduce.scope_ms_per_step``, which knows one segment; the jitted
functions between the two names add segments of their own
(``attn/jit(_gated_attention)/window``), so the names are matched in
order, not side by side."""
from benchmark import span_reduce


def holds(path, names):
    """True where the path's segments hold ``names`` in that order."""
    segments = iter(path.split("/"))
    return all(any(s == name for s in segments) for name in names)


def seconds_per_step(run, names, also=None):
    """Device seconds a step of the forward and backward events whose
    instruction lies under ``names``; None where the HLO names no phase
    or nothing lies there. ``also = (names, prefix)`` adds the events
    under those names whose instruction's own name starts with
    ``prefix``: a kernel the compiler put in an operation's place keeps
    no scope of its own and takes its consumer's, one level up."""
    if span_reduce.phase_seconds(run) is None or not run.summary["steps"]:
        return None

    def inside_of(name, path):
        if span_reduce.phase_of(path) not in ("fwd", "bwd"):
            return False
        return holds(path, names) or (
            also is not None and name.startswith(also[1])
            and holds(path, also[0]))

    inside = {name for name, path in span_reduce.run_paths(run).items()
              if inside_of(name, path)}
    if not inside:
        return None
    total = sum(sec for name, sec in run.summary["op_seconds"].items()
                if name in inside)
    return total / run.summary["steps"]


def ms_per_step(run, names):
    s = seconds_per_step(run, names)
    return None if s is None else 1e3 * s


def roofline_pct(run, names, flops, nbytes, also=None):
    """The least time the chip could take over ``flops`` and ``nbytes``
    (the larger of the two roofs, ``peaks.json``) over the device time
    a step under ``names`` (and ``also``), in percent."""
    from benchmark import peaks
    s = seconds_per_step(run, names, also)
    if not s:
        return None
    peak = peaks.lookup(run.peaks, run.device.device_kind)
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / s
