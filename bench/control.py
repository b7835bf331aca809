#!/usr/bin/env python3
"""Read the compared numbers of the program and of the control, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \\
        [--control-seeds 3]

For every seed: set up the cell's job (one input), run one job through the
compiled program, and read its numbers against the reference (the lower
readings).  For the first ``--control-seeds`` seeds also read the control:
the benchmark's plain solver on the bfloat16 direct operator, for as many
steps as the program took (the upper readings).  The limits in
``bench/workloads/<cell>.json`` are set from these readings; the benchmark's
own runs never run the control.  One JSON line per reading on standard
output, the last one a summary.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2

    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    worst = {"program": {}, "control": {}}
    for i, seed in enumerate(args.seeds):
        job = cell.job_kind.Job(cell.config, cell.traffic, seed)
        t0 = time.perf_counter()
        record = job.run(0)
        job_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        readings = {"program": job.check(0, record)}
        check_s = time.perf_counter() - t0
        if i < args.control_seeds:
            t0 = time.perf_counter()
            readings["control"] = job.control(0, record)
            readings["control_s"] = time.perf_counter() - t0
        for side in ("program", "control"):
            for name, value in readings.get(side, {}).items():
                seen = worst[side].get(name)
                # lower reading: the largest; upper reading: the smallest
                pick = max if side == "program" else min
                worst[side][name] = value if seen is None else pick(seen,
                                                                    value)
        matvecs = len(job.applications(record))
        print(json.dumps(dict(readings, seed=seed, job_s=job_s,
                              check_s=check_s, matvecs=matvecs,
                              window_backend=job.window_backend)),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": worst["program"], "upper": worst["control"],
                      "limits": cell.limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
