"""The trace reduction (bench/trace.py) and the per-layer metric readers, on
a synthetic trace with known answers and on small traces recorded on a
TPU v5e (bench/testdata, made by bench/record_testdata.py)."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness, trace  # noqa: E402

TESTDATA = REPO / "bench" / "testdata"
US = 1000  # ns

# instruction -> [opcode, op_name], as bench.trace.hlo_ops reads them from
# the compiled program
OPS = {"fusion.1": ["fusion", "jit(job)/jit(fused_matvec_tilde)/while/body/"
                    "gather"],
       "fusion.5": ["fusion", "jit(job)/jit(fused_matvec_tilde)/jit(fft)"],
       "convolution.2": ["convolution", "jit(job)/while/body/dot_general"],
       "add.3": ["add", "jit(job)/while/body/add"],
       "sort.1": ["sort", "jit(job)/jit(build_window_geometry)/sort"],
       "fusion.4": ["fusion", "jit(job)/exp"],
       "while.2": ["while", "jit(job)/while"]}
# (instruction text or kernel name, start, end) in microseconds
DEVICE_OPS = [
    ("window_spread", 1000, 1500),  # the first application starts
    ("%fusion.5 = c64[3]{0} fusion(f32[4]{0} %p)", 1500, 2000),
    ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 2000, 2500),
    ("window_spread", 3000, 3500),  # the second one
    ("%while.2 = (s32[]) while(%t)", 5800, 8600),  # a loop around the next
    ("%convolution.2 = f32[2]{0} convolution(f32[2]{0} %a)", 6000, 7000),
    ("%add.3 = f32[9]{0} add(%a, %b)", 6500, 7500),
    ("%sort.1 = s32[9]{0} sort(%k)", 8000, 8500),
    ("%fusion.4 = f32[9]{0} fusion(%c)", 9500, 9900),  # after the window
]


def _event(meta: int, start_us: int, end_us: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US * 1000}"
            f" duration_ps: {(end_us - start_us) * US * 1000} }}")


def synthetic_profile(last_job_end_us: int = 9000):
    """Two jobs with a check between them on the host; device operations
    of every layer, a loop container, and one operation after the window."""
    from jax.profiler import ProfileData

    device = "\n".join(_event(i + 1, s, e)
                       for i, (_, s, e) in enumerate(DEVICE_OPS))
    device_meta = "\n".join(
        f"event_metadata {{ key: {i + 1} value {{ id: {i + 1} "
        f"name: {json.dumps(name)} }} }}"
        for i, (name, _, _) in enumerate(DEVICE_OPS))
    host = "\n".join([_event(1, 1000, 4000), _event(2, 4000, 6000),
                      _event(1, 6000, last_job_end_us),
                      _event(2, last_job_end_us, last_job_end_us + 950)])
    text = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{device} }}
{device_meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "job" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "check" }} }} }}
'''
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_synthetic_trace_reduces_to_known_numbers():
    r = trace.reduce_profile(synthetic_profile(), OPS)
    assert r["window_s"] == pytest.approx(8000e-6)
    assert r["coverage"] == 1.0 and r["devices"] == 1
    # union [1000,2500] [3000,3500] [5800,8600]: the loop counts as busy
    # time, and the 800 us in it with no operation recorded towards its
    # layer
    assert r["busy_s"] == pytest.approx(4800e-6)
    assert r["layer_s"] == pytest.approx({
        "window": 1500e-6, "fft": 500e-6, "dot": 1000e-6, "build": 500e-6,
        "other": 1800e-6})
    assert r["breakdown"]["idle_gaps"] == [
        ["check", pytest.approx(2300e-6)], ["job", pytest.approx(500e-6)],
        ["job", pytest.approx(400e-6)]]
    ops = r["breakdown"]["device_ops"]
    assert [name for name, _ in ops[3:]] == [
        "while.2 jit(job)/while [other]",
        "fusion.5 jit(fused_matvec_tilde)/jit(fft) [fft]",
        "fusion.1 body/gather [window]",
        "sort.1 jit(build_window_geometry)/sort [build]"]
    assert {name for name, _ in ops[:3]} == {
        "window_spread window_spread [window]",
        "convolution.2 body/dot_general [dot]", "add.3 body/add [other]"}
    # split at each spread; the second application runs to the window's end
    assert r["applications"] == [
        pytest.approx({"window": 1000e-6, "fft": 500e-6, "dot": 0.0,
                       "build": 0.0, "other": 0.0}),
        pytest.approx({"window": 500e-6, "fft": 0.0, "dot": 1000e-6,
                       "build": 500e-6, "other": 1800e-6})]


def test_a_trace_cut_short_covers_part_of_the_window():
    """Device operations that stop long before the last job ends (a full
    trace buffer): the traced window is the part the device trace covers."""
    r = trace.reduce_profile(synthetic_profile(last_job_end_us=50_000), OPS)
    assert r["window_s"] == pytest.approx(8900e-6)
    assert r["coverage"] == pytest.approx(8900 / 49_000)
    assert r["layer_s"]["other"] == pytest.approx(2200e-6)
    # the second application does not end inside the traced window
    assert len(r["applications"]) == 1
    assert r["applications"][0]["window"] == pytest.approx(1000e-6)


@pytest.mark.parametrize("instr,op_name,spread,gather", [
    ("window_spread.8", "window_spread.8", True, False),
    ("window_gather.8", "window_gather.8", False, True),
    # the XLA spread: its loop over node tiles, and each tile's scatter
    # expanded into a loop and its body's operations
    ("while.56", "jit(program)/while/body/closed_call/"
     "jit(fused_matvec_tilde)/while", False, False),
    ("while.55", "jit(program)/while/body/closed_call/"
     "jit(fused_matvec_tilde)/while/body/closed_call/scatter-add", True,
     False),
    ("dynamic-update-slice.103", "jit(program)/jit(fused_matvec_tilde)/"
     "scatter-add", True, False),
    ("fusion.9", "jit(program)/jit(fused_matvec_tilde)/while/body/"
     "closed_call/dynamic_slice", False, False),
    ("fusion.904", "jit(program)/jit(fused_matvec_tilde)/while/body/"
     "closed_call/gather", False, True),
    # outside the operator, or another primitive
    ("fusion.10", "jit(program)/while/body/closed_call/cond/branch_1_fun/"
     "scatter", False, False),
    ("fusion.7", "jit(program)/jit(fused_matvec_tilde)/scatter", False,
     False),
    ("fusion.3", "jit(program)/scatter-add", False, False),
])
def test_is_spread(instr, op_name, spread, gather):
    assert trace.is_spread(instr, op_name) is spread
    assert trace.is_gather(instr, op_name) is gather


def test_a_tiled_xla_spread_starts_one_application():
    """The XLA path spreads in a loop over node tiles, whose body slices
    each tile before its scatter: one application starts at the first
    scatter operation after a gather, however many tiles follow."""
    ops = {"dynamic-slice.1": ["dynamic-slice", "jit(p)/jit(fused_matvec_tilde)"
                               "/while/body/closed_call/dynamic_slice"],
           "dus.1": ["dynamic-update-slice", "jit(p)/jit(fused_matvec_tilde)"
                     "/while/body/closed_call/scatter-add"],
           "fusion.2": ["fusion", "jit(p)/jit(fused_matvec_tilde)/jit(fft)"],
           "fusion.3": ["fusion", "jit(p)/jit(fused_matvec_tilde)/while/body"
                        "/closed_call/gather"],
           "convolution.4": ["convolution", "jit(p)/while/body/dot_general"]}
    from jax.profiler import ProfileData

    one = ["dynamic-slice.1", "dus.1"] * 3 + ["fusion.2", "fusion.3",
                                              "convolution.4"]
    names = [f"%{i} = f32[4]{{0}} op()" for i in one * 3]
    device = "\n".join(_event(i + 1, 1000 + 100 * i, 1050 + 100 * i)
                       for i in range(len(names)))
    meta = "\n".join(f"event_metadata {{ key: {i + 1} value {{ id: {i + 1} "
                     f"name: {json.dumps(n)} }} }}"
                     for i, n in enumerate(names))
    end = 1000 + 100 * len(names)
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{device} }}
{meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_event(1, 1000, end)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "job" }} }} }}
'''))
    r = trace.reduce_profile(profile, ops)
    assert len(r["applications"]) == 3
    # each application: three tiles of two window operations and the
    # gather (window), the FFT, and the solver's product; the first tile's
    # slice runs before its scatter, so it counts in the application before
    assert [app["window"] for app in r["applications"]] == pytest.approx(
        [350e-6, 350e-6, 300e-6])
    for app in r["applications"]:
        assert app == pytest.approx({"window": app["window"], "fft": 50e-6,
                                     "dot": 50e-6, "build": 0.0,
                                     "other": 0.0})


def test_time_lost_inside_a_loop_counts_towards_the_innermost_loop():
    """Stretches with no operation recorded (dropped trace buffers) inside
    a window-step loop that runs inside the solver's loop: each part goes
    to the innermost container around it, nothing outside every container
    is assigned, and the intervals come back in order."""
    outer = (0, 10_000, "other", "while.1 jit(p)/while [other]")
    inner = (1000, 5000, "window", "while.2 f/while [window]")
    later = (6000, 7000, "window", "while.3 f/while [window]")
    lost = [(500, 2000), (4000, 6500), (9000, 12_000)]
    assert trace.loop_pieces(lost, [outer, inner, later]) == [
        (500, 1000) + outer[2:], (1000, 2000) + inner[2:],
        (4000, 5000) + inner[2:], (5000, 6000) + outer[2:],
        (6000, 6500) + later[2:], (9000, 10_000) + outer[2:]]
    assert trace.loop_pieces([(0, 100)], []) == []


def test_operations_lost_inside_a_loop_restore_the_application():
    """A window-step loop whose operations were dropped from the trace for
    2 ms: the application keeps its device time, as busy time does."""
    from jax.profiler import ProfileData

    ops = {"while.1": ["while", "jit(p)/jit(fused_matvec_tilde)/while"],
           "dus.1": ["dynamic-update-slice", "jit(p)/jit(fused_matvec_tilde)"
                     "/while/body/closed_call/scatter-add"],
           "fusion.3": ["fusion", "jit(p)/jit(fused_matvec_tilde)/while/body"
                        "/closed_call/gather"]}
    names = ["%while.1 = (s32[]) while(%t)"] + [
        f"%{i} = f32[4]{{0}} op()" for i in ("dus.1", "dus.1", "fusion.3")]
    spans = [(1000, 6000), (1000, 2000), (4000, 5000), (5000, 6000)]
    device = "\n".join(_event(i + 1, s, e) for i, (s, e) in enumerate(spans))
    meta = "\n".join(f"event_metadata {{ key: {i + 1} value {{ id: {i + 1} "
                     f"name: {json.dumps(n)} }} }}"
                     for i, n in enumerate(names))
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{device} }}
{meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_event(1, 1000, 6000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "job" }} }} }}
'''))
    r = trace.reduce_profile(profile, ops)
    assert r["busy_s"] == pytest.approx(5000e-6)
    assert r["layer_s"]["window"] == pytest.approx(5000e-6)
    assert r["applications"] == [pytest.approx(
        {"window": 5000e-6, "fft": 0.0, "dot": 0.0, "build": 0.0,
         "other": 0.0})]
    assert ["while.1 jit(fused_matvec_tilde)/while [window]",
            pytest.approx(2000e-6)] in r["breakdown"]["device_ops"]


def _facts(apps, columns, coverage):
    return {"columns": columns, "trace": {"applications": apps,
                                          "coverage": coverage}}


def test_per_application_weights_each_column_count_by_the_window():
    """A trace cut short holds the degree pass (one column) and two block
    applications (four) of a job of one plus eleven: each count's mean is
    weighted by the window's own mix."""
    apps = [{"window": 1.0}, {"window": 4.0}, {"window": 4.2}]
    columns = [1] + [4] * 11
    assert trace.per_application(_facts(apps, columns, 0.3), "window") \
        == pytest.approx((1.0 + 11 * 4.1) / 12)
    # no application of four columns ends inside the traced window
    assert trace.per_application(_facts(apps[:1], columns, 0.1),
                                 "window") is None
    # a whole window must hold every application
    assert trace.per_application(_facts(apps, columns, 1.0),
                                 "window") is None
    assert trace.per_application(_facts([], columns, 1.0), "window") is None


def test_trace_without_a_job_annotation_is_refused():
    from jax.profiler import ProfileData

    empty = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" }'))
    with pytest.raises(ValueError, match="no 'job' annotation"):
        trace.reduce_profile(empty, {})


@pytest.mark.parametrize("op_name,layer", [
    ("jit(job)/jit(fused_matvec_tilde)/while/body/scatter-add", "window"),
    ("jit(job)/jit(fused_matvec_tilde)/pallas_call", "window"),
    ("jit(job)/while/body/closed_call/jit(fused_matvec_tilde)/jit(fft)",
     "fft"),
    ("jit(job)/while/body/closed_call/dot_general", "dot"),
    ("jit(job)/jit(kmeans)/while/body/dot_general", "other"),
    ("jit(job)/jit(build_window_geometry)/sort", "build"),
    ("jit(job)/jit(eigh)/eigh", "other"),
])
def test_classify(op_name, layer):
    assert trace.classify(op_name) == layer


def test_hlo_ops_reads_instructions_and_metadata():
    text = "\n".join([
        "ENTRY %main (p: f32[4]) -> f32[4] {",
        '  %fusion.7 = f32[4]{0:T(256)} fusion(f32[4]{0} %p), kind=kLoop, '
        'calls=%f, metadata={op_name="jit(job)/jit(fft)" stack_frame_id=3}',
        "  %while.2 = (s32[]{:T(128)}, f32[4]{0}) while((s32[]{:T(128)}, "
        "f32[4]{0}) %t), condition=%c, body=%b",
        "  ROOT %window_gather.1 = f32[4]{0} custom-call(%fusion.7), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(job)/pallas_call"}',
        "}"])
    assert trace.hlo_ops(text) == {
        "fusion.7": ["fusion", "jit(job)/jit(fft)"],
        "while.2": ["while", ""],
        "window_gather.1": ["custom-call", "jit(job)/pallas_call"]}


def test_hlo_ops_inherits_op_names_from_callers():
    """XLA makes some instructions without metadata: a fusion takes the op
    name of its computation's root, an instruction in a loop body that of
    the loop."""
    text = "\n".join([
        "%fused_computation.3 (param_0: f32[4]) -> f32[4] {",
        "  %param_0 = f32[4]{0} parameter(0)",
        "  ROOT %add.9 = f32[4]{0} add(%param_0, %param_0), "
        'metadata={op_name="jit(job)/jit(fused_matvec_tilde)/scatter-add"}',
        "}",
        "%body.4 (p: (s32[], f32[4])) -> (s32[], f32[4]) {",
        "  %p = (s32[], f32[4]{0}) parameter(0)",
        "  %dynamic-update-slice.2 = f32[4]{0} dynamic-update-slice(%p)",
        "  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%dynamic-update-slice.2)",
        "}",
        "ENTRY %main.5 (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %add_fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.3",
        "  %while.7 = (s32[], f32[4]{0}) while(%t), condition=%cond.2, "
        'body=%body.4, metadata={op_name="jit(job)/jit(fused_matvec_tilde)'
        '/while"}',
        "  ROOT %copy.3 = f32[4]{0} copy(%a)",
        "}"])
    ops = trace.hlo_ops(text)
    assert ops["add_fusion.1"] == [
        "fusion", "jit(job)/jit(fused_matvec_tilde)/scatter-add"]
    assert ops["dynamic-update-slice.2"] == [
        "dynamic-update-slice", "jit(job)/jit(fused_matvec_tilde)/while"]
    assert ops["copy.3"] == ["copy", ""]
    assert trace.classify_event("%dynamic-update-slice.2 = f32[4]{0} "
                                "dynamic-update-slice(%p)", ops) == "window"


# (cell, traced window s, busy s, applications split) of the traces
# recorded on a v5e: one job at a tiny size each, the Pallas window path
# (fig5: the degree pass and 30 Lanczos applications) and the XLA one
# (spiral: the degree pass and 10 block applications).
RECORDED = [("fig5.segment", 0.0129119, 0.011357317, 31),
            ("spiral.eigsh_b4", 0.037840739, 0.036327955, 11)]


@pytest.mark.parametrize("cell,window_s,busy_s,applications", RECORDED)
def test_recorded_v5e_trace_reduces(cell, window_s, busy_s, applications):
    from jax.profiler import ProfileData

    path = TESTDATA / f"tiny_v5e_{cell}.xplane.pb"
    assert path.stat().st_size < 1_000_000
    ops = json.loads(path.with_name(
        path.name.replace(".xplane.pb", ".ops.json")).read_text())
    assert {p.name for p in ProfileData.from_file(str(path)).planes} == {
        "/device:TPU:0", "/host:CPU"}
    r = trace.reduce_path(str(path), ops)
    assert r["devices"] == 1 and r["coverage"] == 1.0
    assert r["window_s"] == pytest.approx(window_s, rel=1e-9)
    assert r["busy_s"] == pytest.approx(busy_s, rel=1e-9)
    layers = r["layer_s"]
    # every layer of the job shows, and the window step leads
    assert all(layers[name] > 0 for name in trace.LAYERS)
    assert max(layers, key=layers.get) == "window"
    assert sum(layers.values()) <= busy_s * (1 + 1e-9)
    top = r["breakdown"]["device_ops"]
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10 and {name for name, _ in gaps} <= {
        "job", "check", "harness"}
    assert sum(s for _, s in gaps) <= window_s - busy_s + 1e-9
    apps = r["applications"]
    assert len(apps) == applications
    # every application holds one window step; the split loses no time
    assert all(app["window"] > 0 for app in apps)
    assert sum(app["window"] for app in apps) <= layers["window"] * (1 + 1e-9)


def test_recorded_fig5_trace_runs_kmeans():
    """The segmentation job's labels come from k-means inside the timed
    program, so its operations show in the trace (in ``other``)."""
    from jax.profiler import ProfileData

    path = TESTDATA / "tiny_v5e_fig5.segment.xplane.pb"
    ops = json.loads(path.with_name(
        "tiny_v5e_fig5.segment.ops.json").read_text())
    names = [trace.event_op(ev.name, ops)[2]
             for plane in ProfileData.from_file(str(path)).planes
             if trace.DEVICE_PLANE.match(plane.name)
             for line in plane.lines for ev in line.events]
    kmeans = [n for n in names if "jit(kmeans)" in n]
    assert kmeans and {trace.classify(n) for n in kmeans} == {"other"}


def _read_all(facts):
    return {m["name"]: harness.load_module(
        REPO / "bench" / "metrics" / f"{m['name']}.py").read(facts)
        for m in harness.load_spec(REPO)["per_layer"]}


def test_per_layer_readers_on_the_synthetic_trace():
    reduction = trace.reduce_profile(synthetic_profile(), OPS)
    facts = {"setup_s": 1.0, "window_s": 8e-3, "jobs": 1, "matvecs": 2,
             "columns": [1, 4], "device_kind": "TPU v5 lite",
             "trace": reduction, "window_least_s": {1: 1e-7, 4: 2e-7}}
    assert _read_all(facts) == pytest.approx({
        "device_idle_share": 40.0,
        "window_ms_per_matvec": 1e3 * (1000e-6 + 500e-6) / 2,
        "window_roofline": 100 * 3e-7 / 1500e-6,
        "fft_ms_per_matvec": 1e3 * 500e-6 / 2,
        "dot_ms_per_solve": 1e3 * 1000e-6,
        "matvecs_per_solve": 2.0})


def test_per_layer_readers_on_a_trace_cut_short():
    """Only what the trace covers is read: the applications that end in
    it, and no per-job number."""
    reduction = trace.reduce_profile(
        synthetic_profile(last_job_end_us=50_000), OPS)
    facts = {"setup_s": 1.0, "window_s": 49e-3, "jobs": 1, "matvecs": 3,
             "columns": [1, 1, 1], "device_kind": "TPU v5 lite",
             "trace": reduction, "window_least_s": {1: 1e-7}}
    values = _read_all(facts)
    assert values["dot_ms_per_solve"] is None
    assert values["window_ms_per_matvec"] == pytest.approx(1.0)
    assert values["window_roofline"] == pytest.approx(100 * 1e-7 / 1e-3)
    assert values["fft_ms_per_matvec"] == pytest.approx(0.5)
