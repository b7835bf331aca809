#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout.  The cell's inputs come from ``--seed``;
set-up compiles the cell's own programs (JAX's persistent compilation cache
lives in ``<checkout>/.jax_cache``), then jobs run back to back for
``--seconds``, then one job drawn from the seed is checked against the
plain reference.  With ``--trace 1`` the window runs under the profiler and
the line carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks`` (each compared number with its limit), which are also
the last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits with 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_enable_x64", False)  # float32, as configured
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import harness

    workload = harness.by_name(harness.load_spec(ROOT)["workloads"],
                                args.workload, "workload")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < workload["chips"]:
        print(f"bench: cell {args.workload} needs {workload['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    for name, c in result["checks"].items():
        # a number that is not finite is written as null, and fails
        if not math.isfinite(c["value"]):
            c["value"] = None
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
