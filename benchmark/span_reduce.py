"""From the program's own spans and scope names to per-layer numbers.

Two reductions, beside ``trace_reduce`` (which reads the profiler's
trace and knows nothing of the program):

- **host**: the fused step records a span tree a step in the program's
  own buffers (``mxnet_tpu.trace``): a root ``train.step`` with the
  children ``step.compile`` (on a miss), ``step.prep`` (and under it
  ``step.prep.hyper`` / ``.gather`` / ``.rng``), ``step.dispatch``,
  ``step.writeback``; every node carries its wall time and, where the
  program records it, the thread's CPU time over the same interval
  (attribute ``cpu_ns``). ``step_trees`` drains the buffers ONCE (a
  drain empties them) and keeps the window's steps on the ``run``
  object for every reader; ``span_ms_per_step`` and
  ``blocked_ms_per_step`` reduce them.
- **device**: inside the step program every operation carries its path
  of ``jax.named_scope`` names in the HLO's metadata
  (``metadata={op_name="jit(pure_step)/jvp(forward)/layers/0/attn/..."``):
  the forward pass under ``forward`` (which ``jax.vjp`` writes as
  ``jvp(forward)``), the backward pass under
  ``transpose(jvp(forward))``, the update under ``optimizer``, then the
  blocks' attribute names and the operators' names. ``phase_seconds``
  sums the traced events' own times (``summary["op_seconds"]``) by
  phase; ``scope_seconds`` by a block's name.

A program that lacks a span, the attribute or the scope names (an
older commit; a compile cache filled before the names existed, since
jax leaves metadata out of the cache's key) gives ``None``, never 0:
the reader then reports nothing.
"""
import re

ROOT = "train.step"
PHASES = ("fwd", "bwd", "opt")
# of the device's busy time: above it the phases do not describe the step
UNSCOPED_MAX = 0.05

_OP_PATH = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*'
                      r'metadata=\{[^}]*op_name="([^"]*)"')
_OPERANDS = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s'
                       r'[\w\-]+\(([^()]*)\)')
_REFERENCE = re.compile(r'%([\w.\-]+)')


# ---------------------------------------------------------------------------
# host: the program's span trees
# ---------------------------------------------------------------------------

def group_steps(spans, steps):
    """The last ``steps`` trees rooted at a ``train.step``: ``[(root,
    [descendants]), ...]`` from drained span dicts, by ``parent_id``.
    None where there are fewer roots than steps (tracing off, or a
    buffer that dropped them)."""
    roots = [s for s in spans if s["name"] == ROOT
             and s["parent_id"] is None]
    if not steps or len(roots) < steps:
        return None
    children = {}
    for s in spans:
        children.setdefault(s["parent_id"], []).append(s)
    trees = []
    for root in roots[-steps:]:
        below, frontier = [], [root]
        while frontier:
            node = frontier.pop()
            kids = children.get(node["span_id"], ())
            below.extend(kids)
            frontier.extend(kids)
        trees.append((root, below))
    return trees


def step_trees(run):
    """The window's steps as span trees (``group_steps``), drained once
    and kept on ``run``."""
    if not hasattr(run, "span_trees"):
        try:
            from mxnet_tpu import trace
        except ImportError:
            run.span_trees = None
        else:
            run.span_trees = group_steps(trace.drain(),
                                         run.result["steps"])
    return run.span_trees


def span_ms_per_step(trees, name):
    """Wall time of the spans called ``name`` over the steps, in ms a
    step; None where no step holds one."""
    if not trees:
        return None
    found = [s["dur_us"] for _, below in trees for s in below
             if s["name"] == name and s["dur_us"] is not None]
    if not found:
        return None
    return 1e-3 * sum(found) / len(trees)


def blocked_ms_per_step(trees):
    """Wall less CPU time of the roots, in ms a step: how long the
    thread inside ``step()`` was not running (parked in the runtime, or
    off its core). None where a root carries no ``cpu_ns``."""
    if not trees:
        return None
    total = 0.0
    for root, _ in trees:
        cpu_ns = root["attrs"].get("cpu_ns")
        if cpu_ns is None or root["dur_us"] is None:
            return None
        total += root["dur_us"] * 1e3 - cpu_ns
    return 1e-6 * total / len(trees)


# ---------------------------------------------------------------------------
# device: the step program's scope names
# ---------------------------------------------------------------------------

def op_paths(hlo_text):
    """HLO instruction name -> its ``op_name`` path.

    An instruction that carries one has its own. The TPU's compiler
    also makes instructions of its own, with no metadata: the copies
    and prefetches that move an operand into place (``copy``,
    ``copy-start`` / ``copy-done``, ``slice-start`` / ``slice-done``,
    looked at by hand in this PR: 4-5% of the busy time of both cells,
    each a ``copy-done`` waiting for its data). Such an instruction
    takes the path of the nearest instruction that consumes its result
    and has one: moving an operand belongs to what the operand is moved
    for. What nothing named consumes stays without a path."""
    paths, users = {}, {}
    for line in hlo_text.splitlines():
        m = _OP_PATH.match(line)
        if m:
            paths[m.group(1)] = m.group(2)
        m = _OPERANDS.match(line)
        if m:
            for operand in _REFERENCE.findall(m.group(2)):
                users.setdefault(operand, []).append(m.group(1))

    def nearest_named_user(name):
        seen, frontier = {name}, [name]
        while frontier:
            nearer = []
            for n in frontier:
                for user in users.get(n, ()):
                    if user in named:
                        return named[user]
                    if user not in seen:
                        seen.add(user)
                        nearer.append(user)
            frontier = nearer
        return None

    named = dict(paths)
    for name in users:
        if name not in named:
            path = nearest_named_user(name)
            if path is not None:
                paths[name] = path
    return paths


def run_paths(run):
    """``op_paths`` of the run's step program, parsed once and kept on
    ``run``."""
    if not hasattr(run, "op_paths"):
        run.op_paths = op_paths(run.result.get("hlo_text") or "")
    return run.op_paths


def phase_of(path):
    """``"fwd"``, ``"bwd"``, ``"opt"`` or None: by the outermost
    segment of the path that names a phase. jax 0.9.0 writes what ran
    under ``named_scope("forward")`` inside ``jax.vjp`` as
    ``jvp(forward)/...`` and its transpose, nested scopes kept, as
    ``transpose(jvp(forward))/...``."""
    for segment in path.split("/"):
        if segment.startswith("transpose(") and "forward" in segment:
            return "bwd"
        if segment in ("forward", "jvp(forward)"):
            return "fwd"
        if segment == "optimizer":
            return "opt"
    return None


def phase_seconds(run):
    """``{"fwd": s, "bwd": s, "opt": s, None: s}`` over the traced
    window: the events' own times by the phase of their instruction,
    None the class of the events with no phase (other programs'
    operations, instructions the compiler made without metadata). None
    where there is no trace or the step's HLO names no phase. Kept on
    ``run``."""
    if not hasattr(run, "phase_s"):
        run.phase_s = None
        phases = {name: phase_of(path)
                  for name, path in run_paths(run).items()}
        if run.summary and any(phases.values()):
            out = dict.fromkeys(PHASES + (None,), 0.0)
            for name, sec in run.summary["op_seconds"].items():
                out[phases.get(name)] += sec
            run.phase_s = out
    return run.phase_s


def phase_ms_per_step(run, phase):
    """One phase's device time in ms a step. Raises where the events
    with no phase hold more than ``UNSCOPED_MAX`` of the busy time: the
    three phases then leave out a visible part of the step."""
    by_phase = phase_seconds(run)
    if by_phase is None or not run.summary["steps"]:
        return None
    busy = run.summary["busy_s"]
    if by_phase[None] > UNSCOPED_MAX * busy:
        raise RuntimeError(
            f"{by_phase[None]:.4g} s of the {busy:.4g} s the device was "
            f"busy ran in events whose HLO instruction names no phase "
            f"(over {UNSCOPED_MAX:.0%}): forward, backward and optimizer "
            "do not add up to the step")
    return 1e3 * by_phase[phase] / run.summary["steps"]


def scope_ms_per_step(run, scope):
    """Device time in ms a step of the forward and backward events
    whose path holds the segment ``scope`` (a block's attribute name).
    None where the HLO names no phase or nothing lies under the
    scope."""
    if phase_seconds(run) is None or not run.summary["steps"]:
        return None
    inside = {name for name, path in run_paths(run).items()
              if scope in path.split("/")
              and phase_of(path) in ("fwd", "bwd")}
    if not inside:
        return None
    total = sum(sec for name, sec in run.summary["op_seconds"].items()
                if name in inside)
    return 1e3 * total / run.summary["steps"]
