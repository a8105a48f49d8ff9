"""From a profiler trace to numbers: the one reduction every PR shares.

``read_xplane`` turns an ``.xplane.pb`` (read with nothing but jax) into
plain lists of ``(name, start_ns, duration_ns)``; ``summarize`` turns
those into a steady window on the trace's own clock (``steady_window``:
from the first run of the step's program on the chip to its last, so a
whole number of step periods; no host clock enters a device metric),
the device's busy time in it (the union of the intervals in which an
operation ran), each operation's own time (its interval less what its
children cover, so a ``while`` and its body are not counted twice), the
idle gaps with what the host was in, and the time of one class of
operations. ``classify_hlo`` reads the step's own HLO text and says
which instructions hold a convolution or a matrix product, so that
nothing rests on a category string of the profiler, and ``hlo_flops``
counts those instructions' FLOPs from the shapes the HLO gives.

What a TPU trace looks like (looked at by hand, PR 24): one plane a
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event for
every HLO instruction executed, named by the instruction's whole text
(``%fusion.123 = bf16[...] fusion(...), kind=kLoop, calls=...``; the
name is what stands before `` = ``), and whose line ``XLA Modules``
holds one event a program run; the host's threads are lines of
the plane ``/host:CPU``, and ``jax.profiler.TraceAnnotation`` spans are
events there under the name given. All times are nanoseconds on one
clock.
"""
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def op_name(event_name):
    """The HLO instruction's name out of an ``XLA Ops`` event's name,
    which is the instruction's whole text (``%fusion.12 = bf16[...]
    fusion(...), kind=kLoop, calls=...``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name):
    """The program's name out of an ``XLA Modules`` event's name
    (``jit_pure_step(1234567)``) or out of an HLO text's first line
    (``HloModule jit_pure_step, entry_computation_layout=...``)."""
    name = event_name.strip()
    if name.startswith("HloModule "):
        name = name.split(None, 1)[1]
    return re.split(r"[(,\s]", name, maxsplit=1)[0]


def steady_window(modules, program):
    """``(start_ns, end_ns, periods)``: from the start of the first run
    of ``program`` on a chip's ``XLA Modules`` line to the start of its
    last run. That is a whole number of periods of the steady loop on
    the device's own clock, whatever ran between two steps counted in.
    None where the program ran fewer than two times."""
    starts = sorted(s for n, s, _ in modules if module_name(n) == program)
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1


def read_xplane(path=None, profile=None):
    """``{"devices": {chip: {"ops": [...], "modules": [...]}}, "host":
    [...]}`` with events as ``(name, start_ns, duration_ns)``."""
    if profile is None:
        import jax
        profile = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(int(m.group(1)),
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend(
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.duration_ns > 0)
    return out


def union_ns(events):
    """Total length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def self_times(events):
    """name -> summed own time in ns: each event's duration less the
    part its nested events cover. Events of one line nest or follow one
    another; they do not cross."""
    own = {}
    stack = []  # [name, stop, own_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, ns = stack.pop()
            own[name] = own.get(name, 0.0) + ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return own


def idle_gaps(events, host, until, top=5):
    """The longest gaps between busy intervals, the last one running to
    ``until`` (the window's end), each with the innermost host span that
    covers its middle (``"none"`` where no span does): ``[(host_span,
    gap_ns), ...]``, longest first."""
    gaps, end = [], None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append((end, start - end))
        end = max(end or 0.0, start + dur)
    if end is not None and until > end:
        gaps.append((end, until - end))
    gaps.sort(key=lambda g: -g[1])
    out = []
    for start, ns in gaps[:top]:
        mid = start + ns / 2
        covering = [h for h in host if h[1] <= mid <= h[1] + h[2]]
        name = min(covering, key=lambda h: h[2])[0] if covering else "none"
        out.append((name, ns))
    return out


def summarize(raw, program, classes=None, annotations=None, flops=None):
    """The reduced trace that the per-layer readers take.

    ``program`` names the step's program (``module_name`` of its HLO):
    everything is read inside each chip's ``steady_window`` of it, so
    ``window_s`` and ``steps`` are the trace's own, not the host's.
    ``classes`` maps an HLO instruction's name to its class (``"mxu"``
    for those that hold a convolution or a matrix product); what is not
    in it is ``"other"``. ``annotations``, if given, keeps only the host
    spans of those names for the idle gaps. ``flops`` (``hlo_flops``)
    gives ``mxu_hlo_flops``: the FLOPs a step that the traced events of
    the class ``"mxu"`` hold by the HLO, and ``mxu_unknown`` names those
    whose FLOPs the HLO does not tell (a Mosaic kernel). Window and busy
    time are averaged over the chips that ran the program. Returns None
    where no chip ran it twice: nothing to read, so the readers return
    nothing."""
    classes = classes or {}
    host = raw["host"]
    if annotations is not None:
        host = [h for h in host if h[0] in annotations]
    chips = []
    for d in raw["devices"].values():
        window = steady_window(d["modules"], program)
        if window is None:
            continue
        start, end, periods = window
        chips.append({
            "end": end, "ns": end - start, "periods": periods,
            "ops": [e for e in d["ops"] if start <= e[1] < end],
            "runs": sum(start <= e[1] < end for e in d["modules"])})
    if not any(c["ops"] for c in chips):
        return None
    n = len(chips)
    steps = chips[0]["periods"]
    own, counts = {}, {}
    for c in chips:
        for name, ns in self_times(c["ops"]).items():
            own[name] = own.get(name, 0.0) + ns / n
        for name, _, _ in c["ops"]:
            counts[name] = counts.get(name, 0.0) + 1.0 / n
    by_class = {}
    for name, ns in own.items():
        kind = classes.get(name, "other")
        by_class[kind] = by_class.get(kind, 0.0) + ns
    mxu = [name for name in counts if classes.get(name) == "mxu"]
    flops = flops or {}
    ranked = sorted(own.items(), key=lambda kv: -kv[1])
    return {
        "window_s": sum(c["ns"] for c in chips) / n * 1e-9,
        "steps": steps,
        "chips": n,
        "busy_s": sum(union_ns(c["ops"]) for c in chips) / n * 1e-9,
        "op_seconds": {name: ns * 1e-9 for name, ns in ranked},
        "class_seconds": {k: ns * 1e-9 for k, ns in by_class.items()},
        "module_runs": max(c["runs"] for c in chips),
        "mxu_hlo_flops": sum((flops.get(name) or 0.0) * counts[name]
                             for name in mxu) / steps,
        "mxu_unknown": sorted(name for name in mxu
                              if flops.get(name, 0.0) is None),
        "idle_gaps": [(name, ns * 1e-9) for name, ns in idle_gaps(
            chips[0]["ops"], host, chips[0]["end"])],
    }


def breakdown(summary, classes=None, top=10):
    """The contract's optional ``breakdown``: the device operations that
    took most time (name with its class, seconds a step) and the longest
    idle gaps by what the host was in."""
    classes = classes or {}
    steps = max(summary["steps"], 1)
    ops = list(summary["op_seconds"].items())[:top]
    return {
        "device_ops": [[f"{n}[{classes.get(n, 'other')}]", s / steps]
                       for n, s in ops],
        "idle_gaps": [[n, s] for n, s in summary["idle_gaps"][:top]],
    }


# ---------------------------------------------------------------------------
# which instructions do matrix work, by the step's own HLO
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s"
                          r"([\w\-]+)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SHAPE = re.compile(r"\b[a-z]\w*\[([\d,]*)\]")
_DIM_LABELS = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_LHS_CONTRACTING = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
MXU_OPCODES = ("convolution", "dot")
FUSED = "fusion"


def _parse_hlo(text):
    """computation -> ``[(instruction, opcode, called computations,
    is a Mosaic call, the line)]``, computations in the text's order."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        called = _CALLS.findall(line)
        for c in _BRANCHES.findall(line):
            called.extend(x.strip().lstrip("%") for x in c.split(","))
        mosaic = opcode == "custom-call" and "tpu_custom_call" in line
        comps[cur].append((name, opcode, called, mosaic, line))
    return comps


def classify_hlo(text):
    """name -> ``"mxu"`` for every instruction of the HLO text that is a
    convolution or a dot, that calls (through any depth of fusion, call,
    while or conditional) a computation holding one, or that is a custom
    call to a Mosaic kernel (``tpu_custom_call``: the attention kernels
    are the only ones this program has). Every other instruction is left
    out, which the readers take as ``"other"``."""
    comps = _parse_hlo(text)
    holds = {}

    def comp_holds(c, seen=()):
        if c in holds:
            return holds[c]
        if c in seen or c not in comps:
            return False
        res = any(op in MXU_OPCODES or mosaic
                  or any(comp_holds(x, seen + (c,)) for x in called)
                  for _, op, called, mosaic, _ in comps[c])
        holds[c] = res
        return res

    classes = {}
    for instructions in comps.values():
        for name, op, called, mosaic, _ in instructions:
            if op in MXU_OPCODES or mosaic \
                    or any(comp_holds(x) for x in called):
                classes[name] = "mxu"
    return classes


def _dims(text):
    return [int(d) for d in text.split(",") if d]


def _operands(line, opcode):
    """The text between the parentheses of ``opcode(...)``."""
    start = line.index(opcode + "(") + len(opcode) + 1
    depth, i = 1, start
    while depth and i < len(line):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        i += 1
    return line[start:i - 1]


def _window(line, key, n, default):
    """One attribute of ``window={size=3x3 stride=2x2 pad=1_1x1_1 ...}``
    as a list a spatial dimension (of ``pad`` the low side)."""
    m = re.search(r"window=\{[^}]*\b" + key + r"=([\d_x\-]+)", line)
    if not m:
        return [default] * n
    return [int(v.split("_")[0]) for v in m.group(1).split("x")]


def instruction_flops(opcode, line, shape_of):
    """FLOPs of one ``convolution`` or ``dot`` line at two a
    multiply-add, from the shapes the HLO gives. A dot: the result's
    elements times its contracted dimensions. A convolution: batch times
    output features times the kernel's input features times, in each
    spatial dimension, the pairs of an output position and a kernel
    position that do not fall into a hole that ``lhs_dilate`` opened,
    and never more pairs than the input has positions to set against
    the kernel's. That is a layer's usual count (positions in the
    padding count) for a forward pass and for both of its gradients, and
    it stays a true count where the TPU's compiler writes something else
    as a convolution: a batched product (batch dimensions as dilated
    spatial ones), a strided layer's backward pass (dilated), an outer
    product (a window as wide as its padding). An operand's shape is
    read off the line where it is printed there, else off the operand's
    own line (``shape_of``: name -> dimensions)."""
    out = _dims(_SHAPE.search(line.split(" = ", 1)[1]).group(1))
    inside = _operands(line, opcode)
    inline = [_dims(d) for d in _SHAPE.findall(inside)]
    lhs, rhs = inline[:2] if len(inline) >= 2 else [
        shape_of[n] for n in re.findall(r"%([\w.\-]+)", inside)[:2]]
    work = 2.0
    if opcode == "dot":
        for d in out:
            work *= d
        m = _LHS_CONTRACTING.search(line)
        for axis in _dims(m.group(1)) if m else []:
            work *= lhs[axis]
        return work
    lhs_labels, kernel_labels, out_labels = _DIM_LABELS.search(
        line).groups()
    work *= rhs[kernel_labels.index("i")]
    work *= out[out_labels.index("b")] * out[out_labels.index("f")]
    n = sum(c.isdigit() for c in out_labels)
    size, stride, pad, hole, spread = (
        _window(line, key, n, default) for key, default in (
            ("size", 1), ("stride", 1), ("pad", 0), ("lhs_dilate", 1),
            ("rhs_dilate", 1)))
    for axis in range(n):
        positions = out[out_labels.index(str(axis))]
        pairs = positions * size[axis]
        if hole[axis] > 1:
            pairs = sum(
                (o * stride[axis] + k * spread[axis] - pad[axis])
                % hole[axis] == 0
                for o in range(positions) for k in range(size[axis]))
        work *= min(pairs, lhs[lhs_labels.index(str(axis))] * size[axis])
    return work


def hlo_flops(text):
    """name -> the FLOPs of the convolutions and dots that ONE event of
    that instruction executes: its own if it is one, those inside its
    fused computation if it is a fusion; None for a Mosaic kernel, whose
    FLOPs the HLO does not tell. A ``while``, ``call`` or ``conditional``
    gets nothing of its own: the instructions of its body are events
    themselves."""
    comps = _parse_hlo(text)
    shapes = {c: {name: _dims(m.group(1)) for name, _, _, _, line in ins
                  for m in [_SHAPE.search(line.split(" = ", 1)[1])] if m}
              for c, ins in comps.items()}

    def own(c, op, called, line):
        if op in MXU_OPCODES:
            return instruction_flops(op, line, shapes[c])
        if op == FUSED:
            return sum(own(x, o, cl, l) for x in called
                       for _, o, cl, _, l in comps.get(x, ()))
        return 0.0

    out = {}
    for c, instructions in comps.items():
        for name, op, called, mosaic, line in instructions:
            flops = None if mosaic else own(c, op, called, line)
            if flops is None or flops > 0:
                out[name] = flops
    return out
