"""Fused fastsum engine vs the two-NFFT path / dense oracles + block Lanczos.

The fused pipeline (spread -> rfftn -> multiply -> irfftn -> gather) is
algebraically the real part of the seed two-NFFT matvec, so agreement is
asserted near machine precision — not at kernel-approximation tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SETUP_1, SETUP_2, FastsumParams, dense_normalized_adjacency,
    dense_weight_matrix, eigsh, fused_spectral_multiplier, make_fastsum,
    make_kernel, make_normalized_adjacency, spectral_support,
)
from repro.core.nfft import build_window_geometry, morton_codes
from repro.core import fastsum_exec
from repro.data import spiral

RNG = np.random.default_rng(3)
N_PTS = 300

KERNELS = [
    ("gaussian", dict(sigma=3.5)),
    ("laplacian_rbf", dict(sigma=2.0)),
    ("multiquadric", dict(c=1.0)),
    ("inverse_multiquadric", dict(c=1.0)),
]


def _points(d, n=N_PTS):
    return jnp.asarray(RNG.normal(size=(n, d)) * 2.0)


# --------------------------------------------------- fused vs two-NFFT oracle
@pytest.mark.parametrize("kname,kw", KERNELS)
@pytest.mark.parametrize("d", [1, 2, 3])
def test_fused_matches_two_nfft_path(kname, kw, d):
    """Same operator, two execution engines: agreement ~ machine eps."""
    kern = make_kernel(kname, **kw)
    pts = _points(d)
    params = FastsumParams(n_bandwidth=16, m=4)
    fs = make_fastsum(kern, pts, params)
    x = jnp.asarray(RNG.normal(size=(N_PTS,)))
    fused = fs.matvec_tilde(x)
    ref = fs.matvec_tilde_reference(x)
    rel = float(jnp.max(jnp.abs(fused - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 1e-12, rel


@pytest.mark.parametrize("kname,kw", KERNELS)
@pytest.mark.parametrize("d", [1, 2, 3])
def test_fused_batched_matches_two_nfft_path(kname, kw, d):
    kern = make_kernel(kname, **kw)
    pts = _points(d)
    params = FastsumParams(n_bandwidth=16, m=4)
    fs = make_fastsum(kern, pts, params)
    cols = jnp.asarray(RNG.normal(size=(N_PTS, 5)))
    fused = fs.matvec_tilde(cols)
    ref = fs.matvec_tilde_reference(cols)
    rel = float(jnp.max(jnp.abs(fused - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 1e-12, rel
    # batched columns equal the single-RHS fused matvec
    for i in range(5):
        np.testing.assert_allclose(np.asarray(fused[:, i]),
                                   np.asarray(fs.matvec_tilde(cols[:, i])),
                                   rtol=1e-11, atol=1e-11)


@pytest.mark.parametrize("d,tol", [(1, 1e-5), (2, 1e-5), (3, 1e-5)])
def test_fused_matches_dense_oracle(d, tol):
    """End-to-end accuracy against the dense W (same tier as test_fastsum)."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(d)
    fs = make_fastsum(kern, pts, SETUP_2)
    x = jnp.asarray(RNG.normal(size=(N_PTS,)))
    ref = dense_weight_matrix(kern, pts) @ x
    out = fs.matvec(x)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < tol, rel


def test_fused_separate_targets_matches_reference():
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(3)
    tgt = jnp.asarray(RNG.normal(size=(80, 3)) * 2.0)
    fs = make_fastsum(kern, pts, SETUP_2, target_points=tgt)
    x = jnp.asarray(RNG.normal(size=(N_PTS,)))
    np.testing.assert_allclose(np.asarray(fs.matvec_tilde(x)),
                               np.asarray(fs.matvec_tilde_reference(x)),
                               rtol=1e-11, atol=1e-11)


def test_fused_operator_symmetry():
    """The symmetrized multiplier keeps A = D^-1/2 W D^-1/2 Hermitian."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(3)
    op = make_normalized_adjacency(kern, pts, SETUP_1)
    x = jnp.asarray(RNG.normal(size=(N_PTS,)))
    y = jnp.asarray(RNG.normal(size=(N_PTS,)))
    lhs = float(jnp.vdot(op.matvec(x), y))
    rhs = float(jnp.vdot(x, op.matvec(y)))
    assert abs(lhs - rhs) / abs(lhs) < 1e-12


# ------------------------------------------------- multiplier / geometry unit
def test_multiplier_support_covers_all_nonzeros():
    """The distributed psum block is exactly the multiplier's support."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(3)
    fs = make_fastsum(kern, pts, SETUP_1)
    mult = np.asarray(fs.multiplier_half)
    mask = np.zeros_like(mult, dtype=bool)
    sup = np.ix_(*[np.asarray(s) for s in spectral_support(fs.plan)])
    mask[sup] = True
    assert np.all(mult[~mask] == 0.0)
    # and the block is at most ~half the seed's N^d psum payload
    n_bw = fs.plan.n_bandwidth
    assert mask.sum() <= (n_bw + 1) ** 2 * (n_bw // 2 + 1)


def test_multiplier_is_hermitian_half_spectrum():
    """irfftn(sym(C) . rfftn(g)) must equal Re(ifftn(C . fftn(g)))."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(2)
    fs = make_fastsum(kern, pts, SETUP_1)
    plan = fs.plan
    grid = plan.grid_size
    g = RNG.normal(size=(grid, grid))
    mult_half = np.asarray(fs.multiplier_half)
    out_half = np.fft.irfftn(np.fft.rfftn(g) * mult_half, s=(grid, grid),
                             axes=(0, 1))
    # full-spectrum reference with the *unsymmetrized* embedded multiplier
    phi = np.asarray(plan.deconvolution_grid())
    small = np.asarray(fs.b_hat) / (grid ** 2 * phi * phi)
    emb = np.asarray(jnp.fft.fftfreq(plan.n_bandwidth,
                                     1.0 / plan.n_bandwidth)).astype(int) % grid
    big = np.zeros((grid, grid), dtype=complex)
    big[np.ix_(emb, emb)] = small
    out_full = np.real(np.fft.ifftn(big * np.fft.fftn(g)))
    scale = np.max(np.abs(out_full))
    np.testing.assert_allclose(out_half, out_full, rtol=0, atol=1e-13 * scale)


def test_window_geometry_morton_sorted():
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(3)
    fs = make_fastsum(kern, pts, SETUP_1)
    win = fs.src_window
    perm = np.asarray(win.perm)
    assert sorted(perm.tolist()) == list(range(N_PTS))  # a true permutation
    codes = np.asarray(morton_codes(win.base, fs.plan.grid_size))
    assert np.all(np.diff(codes) >= 0)  # rows in Morton order


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_window_spread_gather_adjoint(backend):
    """<gather(g), x> == <g, spread(x)> for the fused window step, on both
    streaming backends."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(2)
    fs = make_fastsum(kern, pts, SETUP_1)
    plan, win = fs.plan, fs.src_window
    grid = plan.grid_size
    x = jnp.asarray(RNG.normal(size=(N_PTS, 1)))
    g = jnp.asarray(RNG.normal(size=(grid, grid, 1)))
    lhs = float(jnp.vdot(
        fastsum_exec.window_gather(plan, win, g, backend=backend), x))
    rhs = float(jnp.vdot(
        g, fastsum_exec.window_spread(plan, win, x, backend=backend)))
    assert abs(lhs - rhs) / abs(lhs) < 1e-12


# ------------------------------------------------- streaming window backends
@pytest.mark.parametrize("kname,kw", KERNELS)
@pytest.mark.parametrize("d", [1, 2, 3])
def test_pallas_backend_matches_xla(kname, kw, d):
    """Fused matvec parity: streaming pallas (interpret) vs streaming xla,
    all four kernels, d=1..3, single and batched RHS."""
    kern = make_kernel(kname, **kw)
    pts = _points(d, n=150)
    params = FastsumParams(n_bandwidth=16, m=3)
    fs = make_fastsum(kern, pts, params)
    for x in (jnp.asarray(RNG.normal(size=(150,))),
              jnp.asarray(RNG.normal(size=(150, 3)))):
        via_xla = fs.matvec(x, backend="xla")
        via_pallas = fs.matvec(x, backend="pallas")
        rel = float(jnp.max(jnp.abs(via_pallas - via_xla))
                    / jnp.max(jnp.abs(via_xla)))
        assert rel < 1e-10, (kname, d, x.shape, rel)


def test_backend_auto_resolves_and_rejects():
    plan = SETUP_1.nfft_plan(2)
    auto = fastsum_exec.resolve_backend(None, plan, 1, jnp.float32)
    assert auto in ("xla", "pallas")
    assert fastsum_exec.resolve_backend("auto", plan, 1, jnp.float32) == auto
    assert fastsum_exec.resolve_backend("xla", plan, 1, jnp.float32) == "xla"
    with pytest.raises(ValueError):
        fastsum_exec.resolve_backend("cuda", plan, 1, jnp.float32)


def _lowered_shapes(lowered_text):
    """All tensor element counts appearing in a lowered StableHLO module."""
    import re
    counts = []
    for m in re.finditer(r"tensor<((?:\d+x)+)(?:f|i|u|complex)", lowered_text):
        dims = [int(t) for t in m.group(1).split("x") if t]
        counts.append(int(np.prod(dims)))
    return counts


@pytest.mark.parametrize("d,n", [(2, 4000), (3, 1200)])
def test_xla_window_step_never_materializes_update_cube(d, n):
    """The streaming xla path must stay O(tile * taps^d * C): no buffer of
    the retired whole-window path's (n, taps^d, C) update-cube size may
    appear anywhere in the lowered fused matvec.  ``n`` is chosen above the
    tile size so the cube and the streamed tile differ."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(d, n=n)
    params = FastsumParams(n_bandwidth=16, m=4)
    fs = make_fastsum(kern, pts, params)
    assert fastsum_exec._xla_node_tile(n, fs.plan.taps, d) < n
    x = jnp.asarray(RNG.normal(size=(n, 2)))
    lowered = jax.jit(
        lambda mult, src, tgt, xx: fastsum_exec.fused_pipeline(
            fs.plan, mult, src, tgt, xx, backend="xla")
    ).lower(fs.multiplier_half, fs.src_window, fs.tgt_window, x)
    cube_elems = n * fs.plan.taps ** d  # x C would be bigger still
    shapes = _lowered_shapes(lowered.as_text())
    assert shapes, "no tensor shapes parsed from the lowered module"
    assert max(shapes) < cube_elems, (
        f"buffer with {max(shapes)} elements >= cube size {cube_elems}")


def test_unsorted_window_geometry_same_result():
    """Morton ordering is an internal layout choice, not a semantic one."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(2)
    fs = make_fastsum(kern, pts, SETUP_1)
    plan = fs.plan
    # rebuild the geometry unsorted on the same scaled nodes via the perm
    x = jnp.asarray(RNG.normal(size=(N_PTS,)))
    out = fastsum_exec.fused_matvec_tilde(
        plan, fs.multiplier_half, fs.src_window, fs.tgt_window, x)
    ref = fs.matvec_tilde_reference(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-11, atol=1e-11)


# ----------------------------------------------------------- block Lanczos
class TestBlockLanczos:
    @classmethod
    def setup_class(cls):
        pts, _ = spiral(1000, seed=0)
        cls.pts = jnp.asarray(pts)
        cls.kern = make_kernel("gaussian", sigma=3.5)
        cls.a_dense = dense_normalized_adjacency(cls.kern, cls.pts)
        cls.ref = jnp.sort(jnp.linalg.eigvalsh(cls.a_dense))[::-1][:10]

    @pytest.mark.parametrize("setup,eig_tol,block", [
        (SETUP_1, 5e-3, 4),
        (SETUP_2, 5e-8, 4),
        (SETUP_2, 5e-8, 8),
    ])
    def test_fig3_tier_with_fewer_matvecs(self, setup, eig_tol, block):
        """Block Lanczos hits the Fig. 3 accuracy tiers with ~block_size
        fewer operator invocations than scalar Lanczos."""
        op = make_normalized_adjacency(self.kern, self.pts, setup)
        scalar = eigsh(op.matvec, 1000, 10, num_iters=80,
                       key=jax.random.PRNGKey(0))
        blocked = eigsh(op.matvec, 1000, 10, num_iters=80,
                        key=jax.random.PRNGKey(0), block_size=block)
        err = float(jnp.max(jnp.abs(blocked.eigenvalues - self.ref)))
        assert err < eig_tol, err
        assert blocked.num_matvecs < scalar.num_matvecs
        assert blocked.num_matvecs <= -(-80 // block)

    def test_block_residuals(self):
        op = make_normalized_adjacency(self.kern, self.pts, SETUP_2)
        res = eigsh(op.matvec, 1000, 10, num_iters=80,
                    key=jax.random.PRNGKey(0), block_size=4)
        r = (self.a_dense @ res.eigenvectors
             - res.eigenvectors * res.eigenvalues[None, :])
        rn = float(jnp.max(jnp.linalg.norm(r, axis=0)))
        assert rn < 5e-7, rn

    def test_block_matches_dense_eigsh_smallest(self):
        rng = np.random.default_rng(5)
        n = 200
        m = rng.normal(size=(n, n))
        a = jnp.asarray((m + m.T) / 2.0)
        ref = np.sort(np.linalg.eigvalsh(np.asarray(a)))[:4]
        res = eigsh(lambda x: a @ x, n, 4, which="SA", num_iters=160,
                    key=jax.random.PRNGKey(2), block_size=4)
        np.testing.assert_allclose(np.asarray(res.eigenvalues), ref,
                                   rtol=1e-7, atol=1e-7)


# ------------------------------------------- kernel failures are never hidden
def _failing_kernels(monkeypatch):
    from repro.kernels import nfft_window

    def boom(*a, **k):
        raise RuntimeError("forced Mosaic lowering failure")

    monkeypatch.setattr(nfft_window, "window_spread", boom)
    monkeypatch.setattr(nfft_window, "window_gather", boom)


def test_auto_pallas_on_tpu_raises_on_lowering_failure(monkeypatch):
    """backend="auto" on TPU picks the kernels for a grid that fits VMEM,
    and a kernel that fails to lower raises — no silent xla fallback."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(2, n=64)
    fs = make_fastsum(kern, pts, FastsumParams(n_bandwidth=16, m=4))
    x = jnp.asarray(RNG.normal(size=(64, 2)), jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fastsum_exec.resolve_backend(
        "auto", fs.plan, 2, jnp.float32) == "pallas"
    _failing_kernels(monkeypatch)
    for backend in ("auto", None):
        with pytest.raises(RuntimeError, match="forced Mosaic"):
            fastsum_exec.window_spread(fs.plan, fs.src_window, x,
                                       backend=backend)
        with pytest.raises(RuntimeError, match="forced Mosaic"):
            fastsum_exec.window_gather(
                fs.plan, fs.src_window,
                jnp.zeros((fs.plan.grid_size,) * 2 + (2,), jnp.float32),
                backend=backend)


def test_explicit_pallas_lowering_failure_still_raises(monkeypatch):
    """Asking for pallas by name must surface the failure, not degrade."""
    kern = make_kernel("gaussian", sigma=3.5)
    pts = _points(2, n=64)
    fs = make_fastsum(kern, pts, FastsumParams(n_bandwidth=16, m=4))
    x = jnp.asarray(RNG.normal(size=(64, 1)))
    _failing_kernels(monkeypatch)
    with pytest.raises(RuntimeError, match="forced Mosaic"):
        fastsum_exec.window_spread(fs.plan, fs.src_window, x,
                                   backend="pallas")


@pytest.mark.parametrize("d", [1, 2, 3])
def test_window_geometry_stores_the_inverse_permutation(d):
    """The geometry carries the inverse of its Morton permutation, built
    once with it: ``inv == argsort(perm)``."""
    plan = SETUP_1.nfft_plan(d)
    rng = np.random.default_rng(30 + d)
    nodes = jnp.asarray(rng.uniform(-0.25, 0.25, size=(500, d)))
    win = build_window_geometry(plan, nodes)
    perm = np.asarray(win.perm)
    np.testing.assert_array_equal(np.asarray(win.inv), np.argsort(perm))
    assert not np.array_equal(perm, np.arange(500))  # a real permutation
    unsorted = build_window_geometry(plan, nodes, sort=False)
    np.testing.assert_array_equal(np.asarray(unsorted.inv), np.arange(500))
    rows = win.in_row_order()
    assert rows.perm is None and rows.inv is None
    assert rows.base is win.base and rows.weights is win.weights
