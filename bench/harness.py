"""Finds a cell's pieces by name and runs the cell once.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, found from the names in ``BENCHMARK.json``:

* ``configs[].file``                  the configuration (sizes, setup);
* ``bench/traffic/<traffic>.json``    the job kind and its parameters;
* ``bench/workloads/<cell>.json``     the cell's limits on the compared numbers;
* ``bench/jobs/<kind>.py``            the job kind: set-up, one job, the check;
* ``bench/metrics/<metric>.py``       one metric's reader, ``read(facts)``.

A run: set-up (inputs from the seed, the programs compiled), then jobs back
to back until the first job that ends after ``seconds``, then the check of
one job drawn from the seed against the reference.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax

from bench import compare, trace as trace_mod, work


def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path):
    """Import the module in ``path`` under a name of its own."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    job_kind: object  # the module of bench/jobs/<kind>.py
    end_to_end: list  # BENCHMARK.json entries that this cell reports
    per_layer: list
    readers: dict  # metric name -> read(facts)


def load_cell(root: Path, name: str) -> Cell:
    spec = load_spec(root)
    workload = by_name(spec["workloads"], name, "workload")
    config_entry = by_name(spec["configs"], workload["config"],
                            "configuration")
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{workload['traffic']}.json")
        .read_text())
    limits = json.loads(
        (root / "bench" / "workloads" / f"{name}.json").read_text())["limits"]
    end_to_end = [m for m in spec["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in spec["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(
        root / "bench" / "metrics" / f"{m['name']}.py").read
        for m in end_to_end + per_layer}
    return Cell(name=name, chips=int(workload["chips"]), config=config,
                traffic=traffic, limits=limits,
                job_kind=load_module(
                    root / "bench" / "jobs" / f"{traffic['job']}.py"),
                end_to_end=end_to_end, per_layer=per_layer, readers=readers)


def log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), file=sys.stderr,
          flush=True)


def window_least_seconds(config: dict, columns: int,
                         device_kind: str) -> float:
    """Least time of one application's window step, on ``columns``
    columns, at the chip's peaks."""
    fs = config["fastsum"]
    grid = work.grid_size(fs["n_bandwidth"], fs["m"],
                          fs.get("sigma_os", 2.0))
    return work.window_work(config["n"], config["d"], grid, fs["m"],
                            columns).least_seconds(device_kind)


def run(root: Path, name: str, seed: int, seconds: float, traced: bool,
        t_start: float) -> dict:
    """Run cell ``name`` once; returns the result line as a dict."""
    cell = load_cell(root, name)
    devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    with jax.profiler.TraceAnnotation("setup"):
        job = cell.job_kind.Job(cell.config, cell.traffic, seed)
    log(cell=name, seed=seed, window_backend=job.window_backend,
        n=cell.config["n"], d=cell.config["d"])

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    records = []
    setup_s = time.perf_counter() - t_start
    ends = []  # when each job ended, from the window's start
    w0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("job"):
            records.append(job.run(len(records)))
        ends.append(time.perf_counter() - w0)
        if ends[-1] >= seconds:
            break
    window_s = ends[-1]
    if traced:
        jax.profiler.stop_trace()

    ops = trace_mod.hlo_ops(job.compiled.as_text()) if traced else None
    per_job = [job.applications(r) for r in records]
    columns = [c for cols in per_job for c in cols]
    failed = sum(bool(job.failed(r)) for r in records)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    facts = {"setup_s": setup_s, "window_s": window_s, "jobs": len(records),
             "matvecs": len(columns), "columns": columns,
             "device_kind": kind, "trace": None}
    log(jobs=len(records), failed=failed, window_s=window_s,
        setup_s=setup_s, matvecs=facts["matvecs"],
        matvecs_per_job=[len(cols) for cols in per_job],
        job_s=[b - a for a, b in zip([0.0] + ends, ends)],
        memory_peak_bytes=peak)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(records), "failed": failed}
    if traced:
        reduction = trace_mod.reduce_path(trace_mod.find_xplane(trace_dir),
                                          ops, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(trace_coverage=reduction["coverage"],
            traced_window_s=reduction["window_s"],
            traced_applications=len(reduction["applications"]),
            layer_s=json.dumps(reduction["layer_s"]))
        facts["trace"] = reduction
        facts["window_least_s"] = {
            c: window_least_seconds(cell.config, c, kind)
            for c in set(columns)}
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        metrics_spec = cell.per_layer
    else:
        metrics_spec = cell.end_to_end
    metrics = {}
    for m in metrics_spec:
        value = cell.readers[m["name"]](facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    sample = random.Random(seed).randrange(len(records))
    keep = records[sample]
    del records
    job.release(sample)
    with jax.profiler.TraceAnnotation("check"):
        t0 = time.perf_counter()
        numbers = job.check(sample, keep, set(cell.limits))
        log(check_job=sample, check_s=time.perf_counter() - t0)
    checks = compare.verdict(numbers, cell.limits)
    result["correct"] = compare.passed(checks) and failed == 0
    result["metrics"] = metrics
    result["device"] = device
    if traced:
        result["breakdown"] = reduction["breakdown"]
    result["checks"] = checks
    return result
