"""The benchmark's yardstick on the CPU: the plain reference against a dense
numpy product, the copied generators, and the window-step work formula and
peaks table."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import data, work  # noqa: E402
from bench.reference import DirectOperator  # noqa: E402


def _dense_w(points: np.ndarray, sigma: float) -> np.ndarray:
    p = points.astype(np.float64)
    r2 = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1)
    w = np.exp(-r2 / sigma ** 2)
    np.fill_diagonal(w, 0.0)
    return w


@pytest.mark.parametrize("generator,sigma", [
    ({"generator": "spiral", "n": 2000}, 3.5),
    ({"generator": "synthetic_image", "height": 40, "width": 50}, 90.0),
])
def test_reference_matches_dense_product(generator, sigma):
    points = data.make_points(generator, 7)
    w = _dense_w(points, sigma)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(points.shape[0], 3)).astype(np.float32)
    with jax.enable_x64(False):
        ref = DirectOperator(points, sigma, tile=128)
        wx = np.asarray(ref.w(x))
        deg = np.asarray(ref.degrees)
        av = np.asarray(ref.a(x[:, 0]))
    want = w @ x.astype(np.float64)
    assert np.linalg.norm(wx - want) / np.linalg.norm(want) < 1e-5
    want_deg = w.sum(1)
    assert np.linalg.norm(deg - want_deg) / np.linalg.norm(want_deg) < 1e-5
    s = 1.0 / np.sqrt(want_deg)
    want_a = s * (w @ (s * x[:, 0]))
    assert np.linalg.norm(av - want_a) / np.linalg.norm(want_a) < 1e-5


def test_bfloat16_control_departs_from_reference():
    points = data.make_points({"generator": "spiral", "n": 1500}, 3)
    with jax.enable_x64(False):
        ref = DirectOperator(points, 3.5, tile=128)
        low = DirectOperator(points, 3.5, tile=128, precision="bfloat16")
        gap = float(np.linalg.norm(np.asarray(low.degrees - ref.degrees))
                    / np.linalg.norm(np.asarray(ref.degrees)))
    assert gap > 1e-4


def test_generators_are_seeded_and_shape_stable():
    a = data.make_points({"generator": "spiral", "n": 999}, 5)
    b = data.make_points({"generator": "spiral", "n": 999}, 5)
    c = data.make_points({"generator": "spiral", "n": 999}, 6)
    assert a.dtype == np.float32 and a.shape == (999, 3)
    np.testing.assert_array_equal(a, b)
    assert c.shape == a.shape and not np.array_equal(a, c)
    img = data.make_points(
        {"generator": "synthetic_image", "height": 20, "width": 30}, 2 ** 33)
    assert img.shape == (600, 3) and img.min() >= 0 and img.max() <= 255
    assert data.job_seed(2 ** 31 + 5, 3) != data.job_seed(2 ** 31 + 5, 4)


# (d, N, m, channels): the fig5 setup and SETUP_2 at C = 1 and C = 4, and
# small d=1, d=2 setups; on the chip some run Pallas and some XLA.
SETUPS = [(1, 64, 4, 1), (1, 64, 4, 4), (2, 128, 3, 1), (2, 128, 3, 4),
          (3, 16, 2, 1), (3, 16, 2, 4), (3, 32, 4, 1), (3, 32, 4, 4)]


@pytest.mark.parametrize("d,n_bw,m,channels", SETUPS)
def test_window_work_does_not_depend_on_the_backend(d, n_bw, m, channels,
                                                    monkeypatch):
    from repro.core import FastsumParams
    from repro.core import fastsum_exec

    plan = FastsumParams(n_bandwidth=n_bw, m=m).nfft_plan(d)
    assert work.grid_size(n_bw, m) == plan.grid_size
    monkeypatch.setattr(fastsum_exec.jax, "default_backend", lambda: "tpu")
    backend = fastsum_exec.resolve_backend("auto", plan, channels,
                                           np.float32)
    assert backend in ("pallas", "xla")
    n = 10_000
    w = work.window_work(n, d, plan.grid_size, m, channels)
    taps = 2 * m + 1
    padded = plan.grid_size + taps - 1
    assert w.bytes == (2 * n * d * (1 + taps) * 4 + 2 * n * channels * 4
                       + 2 * padded ** d * channels * 4)
    assert w.ops == 2 * n * taps ** d * (d - 1 + 2 * channels)
    assert w.bound("TPU v5 lite") == "memory"


def test_both_backends_occur_among_the_setups(monkeypatch):
    from repro.core import FastsumParams
    from repro.core import fastsum_exec

    monkeypatch.setattr(fastsum_exec.jax, "default_backend", lambda: "tpu")
    seen = {fastsum_exec.resolve_backend(
        "auto", FastsumParams(n_bandwidth=n, m=m).nfft_plan(d), c,
        np.float32) for d, n, m, c in SETUPS}
    assert seen == {"pallas", "xla"}


def test_fig5_window_least_time():
    w = work.window_work(426_400, 3, 32, 2, 1)
    assert w.bytes == 2 * 426_400 * 3 * 6 * 4 + 2 * 426_400 * 4 \
        + 2 * 36 ** 3 * 4
    assert w.least_seconds("TPU v5 lite") == pytest.approx(w.bytes / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v99")
    with pytest.raises(KeyError):
        work.window_work(10, 1, 16, 2, 1).least_seconds("cpu")


def test_label_mismatch_is_blind_to_cluster_names_only():
    from bench import compare

    truth = np.repeat(np.arange(4), 25)
    renamed = (truth + 1) % 4  # the same partition under other names
    assert compare.label_mismatch(renamed, truth, 4) == 0.0
    moved = renamed.copy()
    moved[::10] = (moved[::10] + 1) % 4
    assert compare.label_mismatch(moved, truth, 4) == pytest.approx(0.1)
    assert compare.label_mismatch(np.full(100, 7), truth, 4) == 1.0


def test_reference_kmeans_separates_the_image_regions():
    from bench import compare
    from bench.reference import spectral_labels

    points, truth = data.make_input(
        {"generator": "synthetic_image", "height": 30, "width": 40}, 9)
    # each region's indicator as a column: the rows the eigenvectors give
    # for well separated regions, plus noise
    rng = np.random.default_rng(0)
    vecs = np.eye(4)[truth] + rng.normal(0, 0.05, (truth.size, 4))
    labels = spectral_labels(vecs, 4, seed=2 ** 40 + 1)
    assert compare.label_mismatch(labels, truth, 4) == 0.0
    assert points.shape == (1200, 3)
