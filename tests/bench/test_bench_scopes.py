"""The program's named scopes (``repro.core.scopes``) in small traces
recorded on a TPU v5e (``bench/testdata/scoped``, made by
``bench/record_testdata.py --out-dir bench/testdata/scoped``).

Each scope shows in the device trace, each agrees with the layer that the
op-name rules of :mod:`bench.trace` give, and the first ``spread``-scoped
operation after a ``gather``-scoped one starts the same applications as the
``scatter-add`` marker.  The trace reduction does not read the scopes yet;
these are what it needs before they replace the op-name rules."""

import collections
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import trace  # noqa: E402
from repro.core import scopes  # noqa: E402

SCOPED = REPO / "bench" / "testdata" / "scoped"
CELLS = ["fig5.segment", "spiral.eigsh_b4"]


def _recorded(cell):
    from jax.profiler import ProfileData

    path = SCOPED / f"tiny_v5e_{cell}.xplane.pb"
    ops = json.loads(path.with_name(
        path.name.replace(".xplane.pb", ".ops.json")).read_text())
    return str(path), ProfileData.from_file(str(path)), ops


def scoped_ops(cell):
    """The span of the recorded trace's ``job`` annotation, and the device
    operations inside it, in order: ``(start, end, container, layer,
    scope, spread, gather)``, where ``scope`` is the innermost scope of the
    operation's op name (``None`` outside every scope) and
    ``spread``/``gather`` are the op-name marker's reading."""
    _, profile, ops = _recorded(cell)
    host = [ev for plane in profile.planes if plane.name == trace.HOST_PLANE
            for line in plane.lines for ev in line.events
            if ev.name == "job"]
    lo = min(ev.start_ns for ev in host)
    hi = max(ev.start_ns + ev.duration_ns for ev in host)
    out = []
    for plane in profile.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                start = max(ev.start_ns, lo)
                end = min(ev.start_ns + ev.duration_ns, hi)
                if end <= start:
                    continue
                instr, opcode, op_name = trace.event_op(ev.name, ops)
                # a Pallas kernel's event is named by its instruction; its
                # op name, scope included, is the instruction's metadata
                scope = scopes.innermost(ops.get(instr, ("", ""))[1])
                out.append((start, end, opcode in trace.CONTAINERS,
                            trace.classify_event(ev.name, ops), scope,
                            trace.is_spread(instr, op_name),
                            trace.is_gather(instr, op_name)))
    return lo, hi, sorted(out, key=lambda op: (op[0], -op[1]))


def _self_time(cell):
    """Device time (s) by ``(layer, scope)`` as the reduction counts it: the
    operations' own time, and stretches inside a loop with no operation
    recorded for the innermost loop (:func:`bench.trace.loop_pieces`)."""
    lo, hi, ops = scoped_ops(cell)
    by = collections.Counter()
    lost, boxes, recorded = [], [], lo
    for start, end, container, layer, scope, _, _ in ops:
        if container:
            boxes.append((start, end, layer, scope))
            continue
        if start - recorded >= trace._LOST_NS:
            lost.append((recorded, start))
        recorded = max(recorded, end)
        by[layer, scope] += (end - start) * 1e-9
    if hi - recorded >= trace._LOST_NS:
        lost.append((recorded, hi))
    for start, end, layer, scope in trace.loop_pieces(lost, boxes):
        by[layer, scope] += (end - start) * 1e-9
    return by


@pytest.mark.parametrize("cell", CELLS)
def test_every_scope_shows_in_the_device_trace(cell):
    found = {op[4] for op in scoped_ops(cell)[2]}
    assert found - {None} == set(scopes.SCOPES)


@pytest.mark.parametrize("cell", CELLS)
def test_the_window_kernels_carry_their_scope(cell):
    """Fig. 5 runs the Pallas window kernels, whose events are named by
    the kernel; the spiral runs the XLA window path."""
    _, profile, ops = _recorded(cell)
    kernels = {instr for plane in profile.planes
               if trace.DEVICE_PLANE.match(plane.name)
               for line in plane.lines for ev in line.events
               for instr in [trace.event_op(ev.name, ops)[0]]
               if instr.split(".")[0] in trace.WINDOW_KERNELS}
    if cell == "spiral.eigsh_b4":
        assert kernels == set()
        return
    assert {k.split(".")[0] for k in kernels} == set(trace.WINDOW_KERNELS)
    for instr in kernels:
        want = instr.split(".")[0].removeprefix("window_")
        assert scopes.innermost(ops[instr][1]) == want


@pytest.mark.parametrize("cell", CELLS)
def test_scopes_agree_with_the_op_name_layers(cell):
    """The window step's time lies in ``spread`` and ``gather`` but for
    1%: the column reshape around them and the multiply of ``fft_mid``.
    Those two scopes hold window-step time but for 1%: adds of the pad's
    fold whose op name XLA cut to ``spread/add``, which the op-name rules
    miss.  The FFT pair's time lies in ``fft_mid`` or ``build`` (the
    build's own FFTs), the geometry's in ``build``, the Krylov products'
    in ``krylov`` or ``krylov_orth``."""
    by = _self_time(cell)
    layer = collections.Counter()
    scope = collections.Counter()
    for (lay, sc), s in by.items():
        layer[lay] += s
        scope[sc] += s
    window = by["window", "spread"] + by["window", "gather"]
    assert 0.99 * layer["window"] <= window <= layer["window"]
    assert window >= 0.99 * (scope["spread"] + scope["gather"])
    assert by["fft", "fft_mid"] + by["fft", "build"] == pytest.approx(
        layer["fft"], rel=1e-9)
    assert by["build", "build"] == pytest.approx(layer["build"], rel=1e-9)
    assert by["dot", "krylov"] + by["dot", "krylov_orth"] == pytest.approx(
        layer["dot"], rel=1e-9)
    assert by["dot", "krylov_orth"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_scope_sums_reconcile_with_the_reduction(cell):
    """Summed over scopes, each layer's time is the reduction's
    ``layer_s``."""
    path, _, ops = _recorded(cell)
    layer_s = trace.reduce_path(path, ops)["layer_s"]
    by = _self_time(cell)
    for name in trace.LAYERS:
        assert sum(s for (lay, _), s in by.items() if lay == name) \
            == pytest.approx(layer_s[name], rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("cell", CELLS)
def test_spread_scope_marks_the_same_applications(cell):
    """The first ``spread``-scoped operation after a ``gather``-scoped one
    starts the same applications as the first ``scatter-add`` or Pallas
    spread after a gather: as many, each in the same gap between two of
    the marker's starts.  The scope's start comes first, at the
    permutation into Morton order that opens the spread."""
    by_name, by_scope = [], []
    spreading = scoped = False
    for start, _, _, _, scope, spread, gather in scoped_ops(cell)[2]:
        if gather:
            spreading = False
        elif spread and not spreading:
            spreading = True
            by_name.append(start)
        if scope == scopes.GATHER:
            scoped = False
        elif scope == scopes.SPREAD and not scoped:
            scoped = True
            by_scope.append(start)
    path, _, ops = _recorded(cell)
    assert len(by_scope) == len(by_name) == len(
        trace.reduce_path(path, ops)["applications"])
    for before, scoped_start, start in zip([0] + by_name, by_scope, by_name):
        assert before < scoped_start <= start
