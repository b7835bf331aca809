"""The kernel SSL cells on the CPU at tiny sizes: each job solves the same
instance whatever the seed, the job counts every operator application of
its program, the check catches the planted faults and the bfloat16
control, and the reference and the operator-count reader read what they
should."""

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness, ssl_data, ssl_reference  # noqa: E402
from bench.reference import DirectOperator  # noqa: E402

CELLS = ["crescent.ssl_cg"]
SEED = 2 ** 31 + 11


def run_cell(root: Path, cell: str, seed: int = SEED) -> dict:
    with jax.enable_x64(False):
        return harness.run(root, cell, seed, 0.0, False, time.perf_counter())


def make_job(root: Path, cell: str, seed: int = SEED):
    c = harness.load_cell(root, cell)
    with jax.enable_x64(False):
        return c, c.job_kind.Job(c.config, c.traffic, seed)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_checks_at_tiny_size(tiny_checkout, cell):
    result = run_cell(tiny_checkout, cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"solve_s", "setup_s"}
    limits = json.loads((REPO / "bench" / "workloads" / f"{cell}.json")
                        .read_text())["limits"]
    assert {"degree_rel_err", "ssl_true_residual"} <= set(limits) <= {
        "degree_rel_err", "ssl_true_residual", "ssl_label_mismatch"}
    assert {name: c["limit"] for name, c in result["checks"].items()} \
        == limits


@pytest.mark.parametrize("cell", CELLS)
def test_every_job_solves_the_same_instance(tiny_checkout, cell):
    """The seed orders the nodes and nothing else: two jobs of two seeds
    take the same iterations, and their solutions agree once mapped back to
    the instance's order."""
    _, a = make_job(tiny_checkout, cell, seed=3)
    _, b = make_job(tiny_checkout, cell, seed=2 ** 33 + 1)
    assert not np.array_equal(a.order(0), b.order(0))
    assert not np.array_equal(a.order(0), a.order(1))
    with jax.enable_x64(False):
        ra, rb = a.run(0), b.run(0)
    np.testing.assert_array_equal(np.asarray(ra["num_iters"]),
                                  np.asarray(rb["num_iters"]))
    assert a.applications(ra) == b.applications(rb)
    with jax.enable_x64(False):  # the input made while job 0 ran
        assert a.applications(a.run(1)) == a.applications(ra)

    def back(job, x):
        out = np.empty_like(x)
        out[job.order(0)] = x
        return out

    ua = back(a, np.asarray(ra["u"]))
    ub = back(b, np.asarray(rb["u"]))
    # float32 sums in another order, through the same number of
    # iterations: rounding (6e-8) times cond(I + beta L_s) <= 1 + 2 beta,
    # 1.2e-4 (Fig. 5's four columns read 1.4e-4 at this size)
    assert np.linalg.norm(ua - ub) <= 1e-3 * np.linalg.norm(ua)
    np.testing.assert_array_equal(back(a, np.asarray(ra["labels"])),
                                  back(b, np.asarray(rb["labels"])))


@pytest.mark.parametrize("cell", CELLS)
def test_job_counts_every_operator_application(tiny_checkout, cell,
                                               monkeypatch):
    """The job's list of applications is what its compiled program runs
    (counted by a host callback in every application), and
    ``matvecs_per_solve`` reads the solver's own ``num_iters`` plus the
    degree and exit passes from it."""
    from repro.core.fastsum import FastsumOperator

    calls = []
    matvec = FastsumOperator.matvec

    def counted(self, x, backend=None):
        jax.debug.callback(lambda: calls.append(x.shape))
        return matvec(self, x, backend=backend)

    monkeypatch.setattr(FastsumOperator, "matvec", counted)
    c, job = make_job(tiny_checkout, cell)
    with jax.enable_x64(False):
        record = jax.block_until_ready(job.compiled(*job.inputs(0)))
        jax.effects_barrier()
    columns = job.applications(record)
    assert len(columns) == len(calls)
    assert columns == [1 if len(s) == 1 else s[1] for s in calls]
    iters = int(np.max(np.asarray(record["num_iters"])))
    assert iters > 1
    facts = {"jobs": 1, "matvecs": len(columns), "trace": None}
    assert c.readers["matvecs_per_solve"](facts) == iters + 2


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_checkout, cell):
    c, job = make_job(tiny_checkout, cell, seed=5)
    with jax.enable_x64(False):
        numbers = job.control(0, job.run(0))
    assert any(numbers[name] > limit for name, limit in c.limits.items()), \
        numbers


def _unchanged_state(monkeypatch):
    """Plant the fault 'a step that returns its state unchanged': every
    operator application returns its input."""
    from repro.core.fastsum import FastsumOperator

    monkeypatch.setattr(FastsumOperator, "matvec",
                        lambda self, x, backend=None: x)


def _patch_solve(monkeypatch, alter):
    import repro.graph.ssl

    solve = repro.graph.ssl.kernel_ssl_cg
    monkeypatch.setattr(repro.graph.ssl, "kernel_ssl_cg",
                        lambda *a, **k: alter(solve(*a, **k)))


def _negate_one_column(monkeypatch):
    """Plant the fault 'an answer altered where it is produced': the last
    region's column of the solution negated (the crescent's only one)."""
    def alter(res):
        u = res.u
        return res._replace(u=-u if u.ndim == 1 else u.at[:, -1].multiply(-1))

    _patch_solve(monkeypatch, alter)


def _drop_labels(monkeypatch):
    """Plant the fault 'an input lost on the way': the labelled nodes never
    reach the solve (f = 0)."""
    import jax.numpy as jnp
    import repro.graph.ssl

    make = repro.graph.ssl.training_matrix
    monkeypatch.setattr(repro.graph.ssl, "training_matrix",
                        lambda *a: jnp.zeros_like(make(*a)))


def _stop_after_one_iteration(monkeypatch):
    """Plant the fault 'a loop cut short': CG stops after one iteration."""
    import repro.graph.ssl

    cg = repro.graph.ssl.cg
    monkeypatch.setattr(repro.graph.ssl, "cg",
                        lambda *a, **k: cg(*a, **dict(k, maxiter=1)))


@pytest.mark.parametrize("fault", [_unchanged_state, _negate_one_column,
                                   _drop_labels, _stop_after_one_iteration])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(tiny_checkout, monkeypatch, cell,
                                      fault):
    fault(monkeypatch)
    result = run_cell(tiny_checkout, cell)
    assert not result["correct"], result["checks"]


# The job kind with more than two classes: four one-vs-rest columns on Fig.
# 5's configuration.  Fig. 5's setup puts ~9e-4 of operator error on a
# vector; the true residual reads 3.6e-3 at 90x120 pixels (conftest).
ONE_VS_REST = {"job": "ssl_cg", "instance_seed": 1808045805, "classes": 4,
               "labelled_per_class": 5, "beta": 1000.0, "tol": 1e-4,
               "maxiter": 1000}
ONE_VS_REST_LIMITS = {"degree_rel_err": 1e-3, "ssl_true_residual": 5e-3}


@pytest.mark.parametrize("fault", [None, _negate_one_column, _drop_labels,
                                   _stop_after_one_iteration])
def test_one_vs_rest_job_at_d3(tiny_checkout, monkeypatch, fault):
    """Four columns in one lockstep CG at d = 3: the job counts C = 4
    applications and per-column iterations, its check passes, and each
    planted fault fails it."""
    if fault is not None:
        fault(monkeypatch)
    config = json.loads((tiny_checkout / "bench" / "configs"
                         / "fig5_segmentation.json").read_text())
    kind = harness.load_module(tiny_checkout / "bench" / "jobs" / "ssl_cg.py")
    with jax.enable_x64(False):
        job = kind.Job(config, ONE_VS_REST, SEED)
        record = job.run(0)
        numbers = job.check(0, record)
    iters = np.asarray(record["num_iters"])
    assert job.columns == 4 and iters.shape == (4,)
    assert job.applications(record) == [1] + [4] * (int(iters.max()) + 1)
    passed = all(numbers[k] <= v for k, v in ONE_VS_REST_LIMITS.items())
    assert passed == (fault is None), numbers


def test_reference_cg_solves_the_direct_system():
    """The reference's CG against a dense float64 solve on the same direct
    operator, and its right-hand side against the program's."""
    from repro.graph.ssl import training_matrix

    instance = ssl_data.Instance(
        {"generator": "crescent_fullmoon", "n": 800, "r1": 5.0, "r2": 5.0,
         "r3": 8.0}, 7, 5, 2)
    f = ssl_reference.rhs(instance.given, 2)
    assert f.shape == (800, 1) and np.count_nonzero(f) == 10
    np.testing.assert_array_equal(
        f[:, 0], np.asarray(training_matrix(instance.given, 2)))
    beta = 1e3
    with jax.enable_x64(False):
        ref = DirectOperator(instance.points, 0.75, tile=128)
        u, iters = ssl_reference.cg(ssl_reference.system(ref, beta), f,
                                    tol=1e-5, steps=1000)
        a = np.asarray(ref.a(np.eye(800, dtype=np.float32)), np.float64)
    eye = np.eye(800)
    u_star = np.linalg.solve(eye + beta * (eye - a), f.astype(np.float64))
    assert 0 < iters < 1000
    # cond(I + beta L_s) ~ 2e3 times the stopping tolerance, with float32
    # rounding: well under 1e-1 relative; labels as the dense solve's
    assert np.linalg.norm(u - u_star) < 3e-2 * np.linalg.norm(u_star)
    np.testing.assert_array_equal(ssl_reference.labels(u),
                                  ssl_reference.labels(u_star))
    assert ssl_reference.true_residual(f, u_star, beta, ref) < 1e-3
    assert ssl_reference.true_residual(f, 0 * u_star, beta, ref) == 1.0


def test_instance_is_fixed_by_its_seed():
    spec = {"generator": "synthetic_image", "height": 24, "width": 32}
    a = ssl_data.Instance(spec, 11, 5, 4)
    b = ssl_data.Instance(spec, 11, 5, 4)
    np.testing.assert_array_equal(a.points, b.points)
    np.testing.assert_array_equal(a.given, b.given)
    assert [int(np.sum(a.given == c)) for c in range(4)] == [5] * 4
    assert np.all(a.given[a.given >= 0] == a.classes[a.given >= 0])
    order = a.order(2 ** 40 + 3)
    np.testing.assert_array_equal(np.sort(order), np.arange(a.n))
    assert not np.array_equal(order, a.order(2 ** 40 + 4))


def _reader(name):
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py").read


def test_matvec_count_reads_cg_iterations_plus_two():
    """Two jobs of 40 CG iterations each: per job the degree pass, 40
    lockstep applications and the exit pass, so ``matvecs_per_solve`` less
    2 is the solver's ``num_iters``."""
    job = object.__new__(harness.load_module(
        REPO / "bench" / "jobs" / "ssl_cg.py").Job)
    job.columns = 4
    per_job = job.applications({"num_iters": np.array([40, 37, 40, 12])})
    assert per_job == [1] + [4] * 41
    facts = {"jobs": 2, "matvecs": 2 * len(per_job), "trace": None}
    assert _reader("matvecs_per_solve")(facts) - 2 == 40
