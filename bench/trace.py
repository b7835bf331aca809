"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else:

* the window is the span of the benchmark's own ``job`` annotations on the
  host (``jax.profiler.TraceAnnotation``);
* busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each TPU plane), averaged over devices;
* every device operation is put into one layer by :func:`classify`, from
  the op-name metadata of its instruction in the compiled program
  (:func:`hlo_ops`); loop and branch containers (``while``,
  ``conditional``, ``call``) count towards busy time but not towards a
  layer, since their bodies are traced as operations of their own, except
  where no operation is recorded inside them for 10 us or more
  (:func:`loop_pieces`): that time counts towards the innermost
  container's layer.  The profiler drops whole buffers of operations when
  the host falls behind in reading them, so a trace can lose seconds of a
  loop's operations while the loop's own event runs on; the device was
  running that loop all the while;
* ``breakdown`` lists the operations that took most device time and the
  longest idle gaps, each named by the host annotation it fell in;
* ``applications`` splits the traced window at the start of each operator
  application's spread: the first spread operation (:func:`is_spread`)
  after a gather operation (:func:`is_gather`), or the first of the
  window.  A spread is one Pallas kernel, or many operations (the XLA
  path's loop over node tiles, each tile's scatter expanded into its own
  loop), and no gather runs inside it.  The split gives each layer's time
  in every application that ends inside the traced window.  The interval
  from one spread to the next holds the whole window step and FFT pair of
  the first application; the solver's work between them falls in other
  layers.

The chip's trace buffer holds a bounded number of operations.  A window of
many small operations (the XLA window path issues several per node) fills
it before the window ends; the operations then stop while the host's
``job`` annotation goes on.  The traced window is therefore the part of
the window that the device trace covers (``covered``), and ``coverage`` is
its share of the annotated window.  Per-application numbers are then taken
over the applications that end inside it (:func:`per_application`).
"""

from __future__ import annotations

import bisect
import collections
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATIONS = ("setup", "job", "check")
LAYERS = ("window", "fft", "dot", "build", "other")
CONTAINERS = ("while", "conditional", "call")
WINDOW_KERNELS = ("window_spread", "window_gather")
# a device trace that stops this long before the window ends was cut short
_TRUNCATION_NS = 10_000_000
# inside a loop, a stretch this long with no operation recorded is counted
# as the loop's own (its operations were dropped from the trace); shorter
# stretches between operations are the loop's overhead and stay unassigned
_LOST_NS = 10_000

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) \(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)|"
                    r"branch_computations=\{([^}]*)\}")
_EVENT_INSTR = re.compile(r"^%([\w.\-]+) = ")


def hlo_ops(hlo_text: str) -> dict:
    """``{instruction: [opcode, op_name]}`` of a compiled program's text
    (``compiled.as_text()``).

    XLA makes some instructions without op-name metadata of their own (while
    it rewrites loops and fuses); such an instruction takes the op name of
    the root of the computation it calls (a fusion's), else that of the
    instruction that calls the computation it lies in (a loop body's).
    """
    ops, home, calls, root, caller = {}, {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op_name = _OP_NAME.search(line)
        ops[name] = [m.group(2), op_name.group(1) if op_name else ""]
        home[name] = computation
        if line.lstrip().startswith("ROOT"):
            root[computation] = name
        callees = [c for one, many in _CALLS.findall(line)
                   for c in ([one] if one else re.findall(r"%([\w.\-]+)",
                                                          many))]
        calls[name] = callees
        for callee in callees:
            caller.setdefault(callee, name)

    def inherited(name, seen):
        if ops[name][1] or name in seen:
            return ops[name][1]
        seen.add(name)
        for callee in calls[name]:
            if callee in root and inherited(root[callee], seen):
                return ops[root[callee]][1]
        parent = caller.get(home[name])
        return inherited(parent, seen) if parent else ""

    for name, op in ops.items():
        op[1] = inherited(name, set())
    return ops


def classify(op_name: str) -> str:
    """The layer of an operation, from the op-name metadata that JAX gives
    it: the path of jitted functions and the primitive that made it."""
    if "jit(fft)" in op_name:
        return "fft"
    if "jit(fused_matvec_tilde)" in op_name:
        return "window"
    if "jit(build_window_geometry)" in op_name:
        return "build"
    if op_name.endswith("dot_general") and "jit(kmeans)" not in op_name:
        return "dot"
    return "other"


def event_op(name: str, ops: dict):
    """``(instruction, opcode, op_name)`` of a device event named
    ``name``: the HLO text of an XLA operation, or a Pallas kernel's
    name."""
    m = _EVENT_INSTR.match(name)
    instr = m.group(1) if m else name
    if instr.split(".")[0] in WINDOW_KERNELS:
        return instr, "custom-call", instr
    opcode, op_name = ops.get(instr, ("", ""))
    return instr, opcode, op_name


def _window_primitive(instr: str, op_name: str, kernel: str,
                      primitive: str) -> bool:
    if instr.split(".")[0] == kernel:
        return True
    return ("jit(fused_matvec_tilde)" in op_name
            and op_name.rsplit("/", 1)[-1] == primitive)


def is_spread(instr: str, op_name: str) -> bool:
    """Whether a device operation is part of an operator application's
    spread: the Pallas spread kernel, or an operation made by the
    operator's ``scatter-add`` (the XLA path)."""
    return _window_primitive(instr, op_name, "window_spread", "scatter-add")


def is_gather(instr: str, op_name: str) -> bool:
    """Whether a device operation is part of an operator's gather: the
    Pallas gather kernel, or an operation made by the operator's
    ``gather``."""
    return _window_primitive(instr, op_name, "window_gather", "gather")


def classify_event(name: str, ops: dict) -> str:
    _, _, op_name = event_op(name, ops)
    if op_name.split(".")[0] in WINDOW_KERNELS:
        return "window"
    return classify(op_name)


def _event_facts(name: str, ops: dict) -> tuple:
    """``(gather, spread, container, layer, breakdown key)`` of a device
    event named ``name``."""
    instr, opcode, op_name = event_op(name, ops)
    layer = classify_event(name, ops)
    where = "/".join(op_name.split("/")[-2:])
    return (is_gather(instr, op_name), is_spread(instr, op_name),
            opcode in CONTAINERS, layer, f"{instr} {where} [{layer}]")


def loop_pieces(lost: list, boxes: list) -> list:
    """The parts of ``lost`` (intervals ``(start, end)``, in order, in which
    no operation is recorded) that lie inside a container, each as
    ``(start, end, layer, key)`` of the innermost one.  ``boxes`` are the
    containers ``(start, end, layer, key)``, sorted by start and, where
    starts tie, outermost first; containers on one line nest."""
    pieces, stack, i = [], [], 0
    for t, end in lost:
        while t < end:
            while i < len(boxes) and boxes[i][0] <= t:
                stack.append(boxes[i])
                i += 1
            while stack and stack[-1][1] <= t:
                stack.pop()
            nxt = boxes[i][0] if i < len(boxes) else end
            if not stack:  # outside every container: the device is idle
                t = min(end, nxt)
                continue
            stop = min(end, stack[-1][1], nxt)
            pieces.append((t, stop) + stack[-1][2:])
            t = stop
    return pieces


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _merge(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _host_activity(host, t) -> str:
    """The innermost benchmark annotation that covers host time ``t``."""
    inside = [(e - s, name) for s, e, name in host if s <= t <= e]
    return min(inside)[1] if inside else "harness"


def reduce_profile(profile, ops: dict, n_devices: int = 1) -> dict:
    """Reduce a ``ProfileData`` (see the module docstring); ``ops`` maps
    instruction names to ``[opcode, op_name]`` (:func:`hlo_ops`)."""
    host = []  # (start_ns, end_ns, name)
    devices = []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events if ev.name in ANNOTATIONS]
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(ev.start_ns, ev.start_ns
                                     + ev.duration_ns, ev.name)
                                    for ev in line.events])
    jobs = [(s, e) for s, e, n in host if n == "job"]
    if not jobs:
        raise ValueError("the trace has no 'job' annotation")
    lo, hi = min(s for s, _ in jobs), max(e for _, e in jobs)
    devices = devices[:n_devices]
    count = max(len(devices), 1)
    # the span that every device's trace covers
    ends = [max((min(e, hi) for s, e, _ in ops_ if s < hi), default=lo)
            for ops_ in devices]
    covered = min(ends, default=hi)
    if hi - covered <= _TRUNCATION_NS:
        covered = hi
    layer_ns = dict.fromkeys(LAYERS, 0.0)
    op_ns, busy_ns, gaps, per_device = {}, 0.0, [], []
    facts = {}  # event name -> _event_facts, since names repeat
    for events in devices:
        spans, marks, timed, lost, boxes = [], [], [], [], []
        spreading = False  # inside a spread, since no gather has run
        recorded = lo  # the end of the operations so far
        for start, end, name in sorted(events, key=lambda ev: (ev[0],
                                                                -ev[1])):
            s = start if start > lo else lo
            e = end if end < covered else covered
            if e <= s:
                continue
            spans.append((s, e))
            fact = facts.get(name)
            if fact is None:
                fact = facts[name] = _event_facts(name, ops)
            gather, spread, container, layer, key = fact
            if gather:
                spreading = False
            elif spread and not spreading:
                spreading = True
                if start >= lo:
                    marks.append(start)
            if container:
                boxes.append((s, e, layer, key))
                continue
            if s - recorded >= _LOST_NS:
                lost.append((recorded, s))
            recorded = max(recorded, e)
            layer_ns[layer] += e - s
            timed.append((s, e, layer))
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
        if covered - recorded >= _LOST_NS:
            lost.append((recorded, covered))
        for s, e, layer, key in loop_pieces(lost, boxes):
            layer_ns[layer] += e - s
            timed.append((s, e, layer))
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
        # the last application ends inside the traced window only where
        # the trace covers the whole window
        per_device.append(_applications(
            sorted(marks) + ([covered] if covered == hi else []), timed))
        merged = _merge(spans)
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [x for se in merged for x in se] + [covered]
        gaps += [(e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                 if e > s]
    # the longest first, in trace order where lengths tie
    gaps = [(ns, _host_activity(host, (s + e) / 2))
            for ns, s, e in heapq.nlargest(10, gaps, key=lambda g: g[0])]
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (covered - lo) * 1e-9,
        "busy_s": busy_ns / count * 1e-9,
        "coverage": (covered - lo) / (hi - lo) if hi > lo else 1.0,
        "devices": len(devices),
        "layer_s": {k: v / count * 1e-9 for k, v in layer_ns.items()},
        "applications": [
            {k: sum(d[i][k] for d in per_device) / count * 1e-9
             for k in LAYERS}
            for i in range(min((len(d) for d in per_device), default=0))],
        "breakdown": {
            "device_ops": [[k, v / count * 1e-9] for k, v in top_ops],
            "idle_gaps": [[name, ns * 1e-9] for ns, name in gaps],
        },
    }


def _applications(bounds: list, timed: list) -> list:
    """Each layer's time (ns) between consecutive ``bounds``, from
    ``timed`` operations ``(start, end, layer)``; an operation counts in
    the interval in which it starts."""
    timed = sorted(timed)
    starts = [s for s, _, _ in timed]
    out = []
    for a, b in zip(bounds, bounds[1:]):
        ns = dict.fromkeys(LAYERS, 0.0)
        for s, e, layer in timed[bisect.bisect_left(starts, a):
                                 bisect.bisect_left(starts, b)]:
            ns[layer] += e - s
        out.append(ns)
    return out


def per_application(facts: dict, layer: str):
    """Mean device time (s) of ``layer`` per operator application.

    Taken over the applications that end inside the traced window, for each
    column count apart, and weighted by how many applications of each count
    the whole window ran (``facts["columns"]``: the column count of every
    application, in order).  ``None`` where the trace holds none, or none of
    some count, or more applications than the window ran.
    """
    trace = facts.get("trace")
    apps = trace["applications"] if trace else []
    columns = facts["columns"]
    if not apps or len(apps) > len(columns) or (
            trace["coverage"] == 1.0 and len(apps) != len(columns)):
        return None
    seen = collections.defaultdict(list)
    for cols, app in zip(columns, apps):
        seen[cols].append(app[layer])
    counts = collections.Counter(columns)
    if set(seen) != set(counts):
        return None
    return sum(n * sum(seen[c]) / len(seen[c])
               for c, n in counts.items()) / len(columns)


def reduce_path(path: str, ops: dict, n_devices: int = 1) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), ops, n_devices)
