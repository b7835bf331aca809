"""The harness on the CPU at tiny sizes: every cell's job and check run end
to end, the control and the planted faults come out not correct, and a
configuration, a cell and a metric added as files are found by name."""

import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402

# The cells' configurations, cut to a size a test run can hold: only the
# data shrinks; kernel, setup, traffic and limits are the cells' own.
TINY_DATA = {
    "fig5_segmentation": {"generator": "synthetic_image", "height": 24,
                          "width": 32},
    "spiral_setup2": {"generator": "spiral", "n": 1500},
}
CELLS = ["fig5.segment", "spiral.eigsh_b4"]


def tiny_root(tmp_path: Path) -> Path:
    """A checkout-like directory: the benchmark's files with the
    configurations' data made tiny."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for entry in harness.load_spec(root)["configs"]:
        path = root / entry["file"]
        config = json.loads(path.read_text())
        config["data"] = TINY_DATA[entry["name"]]
        config["n"] = (config["data"]["n"] if "n" in config["data"] else
                       config["data"]["height"] * config["data"]["width"])
        path.write_text(json.dumps(config))
    return root


def run_cell(root: Path, cell: str, seed: int = 2 ** 31 + 11) -> dict:
    with jax.enable_x64(False):
        return harness.run(root, cell, seed, 0.0, False, time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_checks_at_tiny_size(tmp_path, cell):
    result = run_cell(tiny_root(tmp_path), cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"solve_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    limits = json.loads((REPO / "bench" / "workloads" / f"{cell}.json")
                        .read_text())["limits"]
    assert {name: c["limit"] for name, c in result["checks"].items()} \
        == limits


def test_jobs_make_each_input_from_the_seed_and_keys_on_the_host():
    from bench import data
    from bench.jobs import eigsh as eigsh_job

    s = data.job_seed(2 ** 31 + 11, 3)
    with jax.enable_x64(False):
        want = jax.random.key_data(jax.random.PRNGKey(s % 2 ** 31))
    assert (eigsh_job.input_key(s) == want).all()


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, cell):
    root = tiny_root(tmp_path)
    c = harness.load_cell(root, cell)
    with jax.enable_x64(False):
        job = c.job_kind.Job(c.config, c.traffic, 5)
        numbers = job.control(0, job.run(0))
    assert any(numbers[name] > limit for name, limit in c.limits.items()), \
        numbers


def _patch_eigsh(monkeypatch, alter):
    import repro.core

    eigsh = repro.core.eigsh
    monkeypatch.setattr(repro.core, "eigsh",
                        lambda *a, **k: alter(eigsh(*a, **k)))


def _shift_eigenvalues(monkeypatch):
    """Plant the fault 'an answer altered where it is produced': every
    eigenvalue shifted by 5e-2, the vectors left as they are."""
    _patch_eigsh(monkeypatch, lambda res: res._replace(
        eigenvalues=res.eigenvalues + 5e-2))


def _negate_half_vector(monkeypatch):
    """Plant the fault 'an answer altered where it is produced': half of
    the entries of one eigenvector negated."""
    def alter(res):
        vecs = res.eigenvectors
        return res._replace(
            eigenvectors=vecs.at[: vecs.shape[0] // 2, -1].multiply(-1.0))

    _patch_eigsh(monkeypatch, alter)


def _alter_labels(monkeypatch):
    """Plant the fault 'an answer altered where it is produced': the
    cluster of every tenth point moved to the next cluster."""
    import jax.numpy as jnp
    import repro.graph.spectral

    spectral = repro.graph.spectral.spectral_clustering

    def altered(adjacency, k, **kw):
        res = spectral(adjacency, k, **kw)
        a = res.assignments
        moved = jnp.where(jnp.arange(a.shape[0]) % 10 == 0, (a + 1) % k, a)
        return res._replace(assignments=moved)

    monkeypatch.setattr(repro.graph.spectral, "spectral_clustering",
                        altered)


def _unchanged_state(monkeypatch):
    """Plant the fault 'a step that returns its state unchanged': every
    operator application returns its input."""
    from repro.core.fastsum import FastsumOperator

    monkeypatch.setattr(FastsumOperator, "matvec",
                        lambda self, x, backend=None: x)


# The faults each cell can have: the cells run on one chip, so none has an
# exchange between chips to leave out, and an eigensolve has no batch of
# which half could be left out.  Only fig5.segment returns labels; its
# eigenvalues are compared by no number (PERF.md), so a shift of them
# alone is a fault of spiral.eigsh_b4 only.
@pytest.mark.parametrize("cell,fault", [
    ("fig5.segment", _negate_half_vector),
    ("fig5.segment", _alter_labels),
    ("fig5.segment", _unchanged_state),
    ("spiral.eigsh_b4", _shift_eigenvalues),
    ("spiral.eigsh_b4", _negate_half_vector),
    ("spiral.eigsh_b4", _unchanged_state),
])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, cell, fault):
    root = tiny_root(tmp_path)
    fault(monkeypatch)
    result = run_cell(root, cell)
    assert not result["correct"], result["checks"]


def test_new_config_cell_and_metric_are_found_by_name(tmp_path):
    """A later PR adds files and entries; it edits none that exist."""
    root = tmp_path / "checkout"
    (root / "bench" / "jobs").mkdir(parents=True)
    (root / "bench" / "metrics").mkdir()
    for sub in ("configs", "traffic", "workloads"):
        (root / "bench" / sub).mkdir()
    shutil.copy(REPO / "bench" / "jobs" / "eigsh.py",
                root / "bench" / "jobs" / "eigsh.py")
    for m in ("solve_s", "setup_s"):
        shutil.copy(REPO / "bench" / "metrics" / f"{m}.py",
                    root / "bench" / "metrics" / f"{m}.py")
    (root / "bench" / "metrics" / "jobs_per_minute.py").write_text(
        "def read(facts):\n    return 60.0 * facts['jobs'] / "
        "facts['window_s']\n")
    (root / "bench" / "configs" / "tiny_blobs.json").write_text(json.dumps({
        "name": "tiny_blobs", "data": {"generator": "spiral", "n": 700},
        "n": 700, "d": 3, "kernel": "gaussian", "sigma": 3.5,
        "fastsum": {"n_bandwidth": 16, "m": 2, "eps_b": 0.0}}))
    (root / "bench" / "traffic" / "eig2.json").write_text(json.dumps({
        "job": "eigsh", "k": 2, "block_size": 1, "kmeans": False}))
    (root / "bench" / "workloads" / "tiny.eig2.json").write_text(json.dumps({
        "limits": {"degree_rel_err": 1e-2, "eig_residual_excess": 1e-2}}))
    metric = {"unit": "s", "better": "lower", "bound": 0.25,
              "source": "host_clock"}
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny_blobs", "file":
                     "bench/configs/tiny_blobs.json"}],
        "workloads": [{"name": "tiny.eig2", "config": "tiny_blobs",
                       "traffic": "eig2", "chips": 1}],
        "end_to_end": [dict(metric, name="solve_s"),
                       dict(metric, name="setup_s"),
                       dict(metric, name="jobs_per_minute", unit="1/min",
                            better="higher", workloads=["tiny.eig2"])],
        "per_layer": []}))
    result = run_cell(root, "tiny.eig2", seed=3)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"solve_s", "setup_s",
                                      "jobs_per_minute"}
    assert math.isclose(result["metrics"]["jobs_per_minute"]["value"],
                        60.0 / result["metrics"]["solve_s"]["value"])


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "fig5.segment", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout == "" and "needs 1 TPU" in proc.stderr
