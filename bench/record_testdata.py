#!/usr/bin/env python3
"""Record the small profiler traces that the trace-reduction tests read.

    python3 bench/record_testdata.py [--out-dir bench/testdata]

On one chip, for every cell: the cell's job at a tiny size (the cell's own
kernel, setup and traffic; only the data shrinks), run once to warm up,
then once more under the profiler inside the benchmark's ``job``
annotation, followed by a short ``check`` annotation.  Each trace is kept
as ``tiny_v5e_<cell>.xplane.pb``, cut down by :func:`slim` to what the
reduction reads, with its program's operations beside it as
``tiny_v5e_<cell>.ops.json``.  Prints each reduction.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# small enough that the XLA window path's per-node operations keep each
# trace under 1 MB
TINY_DATA = {
    "fig5_segmentation": {"generator": "synthetic_image", "height": 16,
                          "width": 20},
    "spiral_setup2": {"generator": "spiral", "n": 100},
}
CELLS = ("fig5.segment", "spiral.eigsh_b4")
_INSTR_TEXT = re.compile(r"^(%[\w.\-]+ = ).*?\s([a-z][\w\-]*)\(")


def slim(profile, start_ns: float, end_ns: float) -> bytes:
    """The serialized trace of what :mod:`bench.trace` reads between two
    host times: each TPU plane's ``XLA Ops`` line and the host's benchmark
    annotations, with each XLA operation's name cut to its instruction and
    opcode (``%fusion.5 = fusion``).  Times, durations and the order of
    events are kept as recorded."""
    from jax.profiler import ProfileData

    from bench import trace

    planes = []
    for plane in profile.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            keep = [ln for ln in plane.lines if ln.name == trace.OPS_LINE]
            wanted = None
        elif plane.name == trace.HOST_PLANE:
            keep, wanted = list(plane.lines), trace.ANNOTATIONS
        else:
            continue
        names, lines = {}, []
        for line in keep:
            events = []
            for ev in line.events:
                if not start_ns <= ev.start_ns <= end_ns:
                    continue
                if wanted is not None and ev.name not in wanted:
                    continue
                m = _INSTR_TEXT.match(ev.name)
                name = f"{m.group(1)}{m.group(2)}" if m else ev.name
                meta = names.setdefault(name, len(names) + 1)
                events.append(
                    f"events {{ metadata_id: {meta} "
                    f"offset_ps: {round(ev.start_ns * 1000)} "
                    f"duration_ps: {round(ev.duration_ns * 1000)} }}")
            if events:
                lines.append(f'lines {{ id: {len(lines) + 1} '
                             f'name: {json.dumps(line.name)} timestamp_ns: 0 '
                             + " ".join(events) + " }")
        metadata = " ".join(
            f"event_metadata {{ key: {i} value {{ id: {i} "
            f"name: {json.dumps(name)} }} }}" for name, i in names.items())
        planes.append(f"planes {{ id: {len(planes) + 1} "
                      f"name: {json.dumps(plane.name)} "
                      + " ".join(lines) + " " + metadata + " }")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


def write_testdata(profile, start_ns: float, end_ns: float, hlo_text: str,
                   out: Path) -> dict:
    """Write ``out`` (``.xplane.pb``) and its ``.ops.json``; returns the
    reduction of the written trace."""
    from jax.profiler import ProfileData

    from bench import trace

    out.write_bytes(slim(profile, start_ns, end_ns))
    written = ProfileData.from_file(str(out))
    seen = {trace.event_op(ev.name, {})[0] for plane in written.planes
            for line in plane.lines for ev in line.events}
    ops = {k: v for k, v in trace.hlo_ops(hlo_text).items() if k in seen}
    ops_path = Path(str(out).replace(".xplane.pb", ".ops.json"))
    ops_path.write_text(json.dumps(ops, sort_keys=True))
    return trace.reduce_path(str(out), ops)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out-dir", default=str(ROOT / "bench" / "testdata"))
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.profiler import ProfileData

    jax.config.update("jax_enable_x64", False)
    from bench import harness, trace

    os.makedirs(args.out_dir, exist_ok=True)
    for name in CELLS:
        cell = harness.load_cell(ROOT, name)
        config = dict(cell.config, data=TINY_DATA[cell.config["name"]])
        job = cell.job_kind.Job(config, cell.traffic, 1)
        job.run(0)
        tmp = tempfile.mkdtemp(prefix="bench-record-")
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("job"):
            record = job.run(1)
        with jax.profiler.TraceAnnotation("check"):
            time.sleep(0.002)
        jax.profiler.stop_trace()
        profile = ProfileData.from_file(trace.find_xplane(tmp))
        out = Path(args.out_dir) / f"tiny_v5e_{name}.xplane.pb"
        reduction = write_testdata(profile, 0, float("inf"),
                                   job.compiled.as_text(), out)
        shutil.rmtree(tmp, ignore_errors=True)
        print(json.dumps({"cell": name,
                          "device": jax.devices()[0].device_kind,
                          "columns": job.applications(record),
                          "bytes": os.path.getsize(out),
                          "reduction": reduction}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
