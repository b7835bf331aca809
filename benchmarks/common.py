"""Shared benchmark harness: timing, CSV emission, result registry."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

# The paper's accuracy tiers (setup #3: <1e-14 eigenvalue error) need
# float64, which the CPU has.  A TPU has no float64, and its Pallas window
# kernels take float32 only: on the chip every benchmark runs in float32.
ON_TPU = jax.default_backend() == "tpu"
jax.config.update("jax_enable_x64", not ON_TPU)
enable_compile_cache()


def precision() -> str:
    return "float64" if jax.config.jax_enable_x64 else "float32"

RESULTS_DIR = os.environ.get("REPRO_BENCH_DIR", "experiments/bench")


@dataclasses.dataclass
class Row:
    bench: str
    case: str
    value: float
    unit: str
    extra: dict = dataclasses.field(default_factory=dict)

    def format(self) -> str:
        extras = " ".join(f"{k}={v}" for k, v in self.extra.items())
        return f"{self.bench:28s} {self.case:42s} {self.value:>12.6g} {self.unit:10s} {extras}"


class Reporter:
    def __init__(self, name: str):
        self.name = name
        self.rows: list[Row] = []
        dev = jax.devices()[0]
        print(f"# {name}: platform={dev.platform} kind={dev.device_kind} "
              f"count={jax.device_count()} precision={precision()}",
              flush=True)

    def add(self, case: str, value: float, unit: str, **extra) -> None:
        row = Row(self.name, case, float(value), unit, extra)
        self.rows.append(row)
        print(row.format(), flush=True)

    def save(self) -> str:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        path = os.path.join(RESULTS_DIR, f"{self.name}.json")
        with open(path, "w") as f:
            json.dump([dataclasses.asdict(r) for r in self.rows], f, indent=1)
        return path


def timeit(fn: Callable[[], Any], *, warmup: int = 1, repeats: int = 3
           ) -> tuple[float, Any]:
    """Median wall time (s) of fn(); blocks on jax arrays."""
    out = None
    for _ in range(warmup):
        out = fn()
        jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), out


def quick() -> bool:
    """Quick mode is the default; REPRO_BENCH_FULL=1 opts into full sweeps.

    ``QUICK=1`` (the CI smoke job's convention) forces quick mode even if
    REPRO_BENCH_FULL is set.
    """
    if os.environ.get("QUICK") == "1":
        return True
    return os.environ.get("REPRO_BENCH_FULL", "0") != "1"
