"""Lanczos / eigsh correctness + reproduction of the paper's Fig. 3 tiers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SETUP_1, SETUP_2, SETUP_3, dense_normalized_adjacency, eigsh,
    eigsh_smallest_laplacian, make_kernel, make_normalized_adjacency,
)
from repro.data import spiral


def test_eigsh_matches_numpy_dense():
    rng = np.random.default_rng(0)
    n = 300
    m = rng.normal(size=(n, n))
    a = jnp.asarray((m + m.T) / 2.0)
    ref = np.sort(np.linalg.eigvalsh(np.asarray(a)))[::-1][:6]
    res = eigsh(lambda x: a @ x, n, 6, num_iters=120, key=jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(res.eigenvalues), ref, rtol=1e-10, atol=1e-10)
    # eigenvector residuals
    r = a @ res.eigenvectors - res.eigenvectors * res.eigenvalues[None, :]
    assert float(jnp.max(jnp.linalg.norm(r, axis=0))) < 1e-8


def test_eigsh_smallest():
    rng = np.random.default_rng(1)
    n = 200
    m = rng.normal(size=(n, n))
    a = jnp.asarray((m + m.T) / 2.0)
    ref = np.sort(np.linalg.eigvalsh(np.asarray(a)))[:4]
    res = eigsh(lambda x: a @ x, n, 4, which="SA", num_iters=120,
                key=jax.random.PRNGKey(2))
    np.testing.assert_allclose(np.asarray(res.eigenvalues), ref, rtol=1e-9, atol=1e-9)


class TestPaperFigure3Tiers:
    """NFFT-based Lanczos reproduces the paper's three accuracy setups.

    Paper values (spiral data, sigma=3.5, 10 largest eigenpairs of A):
      setup #1 (N=16, m=2): eig err ~1e-4..1e-3, residuals ~1e-4..1e-3
      setup #2 (N=32, m=4): eig err ~1e-10..1e-9, residuals ~1e-8
      setup #3 (N=64, m=7): eig err <1e-14,      residuals 1e-15..1e-13
    """

    @classmethod
    def setup_class(cls):
        pts, _ = spiral(1000, seed=0)
        cls.pts = jnp.asarray(pts)
        cls.kern = make_kernel("gaussian", sigma=3.5)
        cls.a_dense = dense_normalized_adjacency(cls.kern, cls.pts)
        cls.ref = jnp.sort(jnp.linalg.eigvalsh(cls.a_dense))[::-1][:10]

    @pytest.mark.parametrize("setup,eig_tol,res_tol", [
        (SETUP_1, 5e-3, 1e-2),
        (SETUP_2, 5e-8, 5e-7),
        (SETUP_3, 1e-13, 1e-12),
    ])
    def test_tier(self, setup, eig_tol, res_tol):
        op = make_normalized_adjacency(self.kern, self.pts, setup)
        res = eigsh(op.matvec, self.pts.shape[0], 10, num_iters=80,
                    key=jax.random.PRNGKey(0))
        err = float(jnp.max(jnp.abs(res.eigenvalues - self.ref)))
        assert err < eig_tol, err
        r = (self.a_dense @ res.eigenvectors
             - res.eigenvectors * res.eigenvalues[None, :])
        rn = float(jnp.max(jnp.linalg.norm(r, axis=0)))
        assert rn < res_tol, rn

    def test_smallest_laplacian_equals_one_minus_largest(self):
        op = make_normalized_adjacency(self.kern, self.pts, SETUP_2)
        res = eigsh_smallest_laplacian(op.matvec, self.pts.shape[0], 5,
                                       num_iters=60, key=jax.random.PRNGKey(3))
        np.testing.assert_allclose(np.asarray(res.eigenvalues),
                                   1.0 - np.asarray(self.ref[:5]),
                                   rtol=0, atol=1e-7)
        # lambda_1(L_s) = 0 within accuracy
        assert abs(float(res.eigenvalues[0])) < 1e-7


def test_block_eigsh_v0_wider_than_shrunk_block():
    """eigsh(block_size>1, v0=...) used to raise a bare AssertionError when
    the block-shrinking loop reduced the block below v0's column count
    (small n, non-dividing block); v0 must be sliced instead."""
    rng = np.random.default_rng(7)
    n, k = 10, 4
    m = rng.normal(size=(n, n))
    a = jnp.asarray((m + m.T) / 2.0)
    v0 = jnp.asarray(rng.normal(size=(n, 8)))  # shrinks to block_size=5
    res = eigsh(lambda x: a @ x, n, k, v0=v0, block_size=8, num_iters=n)
    ref = np.sort(np.linalg.eigvalsh(np.asarray(a)))[::-1][:k]
    np.testing.assert_allclose(np.asarray(res.eigenvalues), ref,
                               rtol=1e-8, atol=1e-8)


def _adjacency(d: int, dtype):
    """A fastsum adjacency of 400 uniform points: its ``matvec`` carries
    the Morton node order (``repro.core.node_order``)."""
    from repro.core import FastsumParams

    pts = jax.random.uniform(jax.random.PRNGKey(d), (400, d), dtype=dtype)
    return make_normalized_adjacency(make_kernel("gaussian", sigma=0.3), pts,
                                     FastsumParams(n_bandwidth=16, m=3))


@pytest.mark.parametrize("x64", [True, False], ids=["float64", "float32"])
@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("d", [2, 3])
def test_eigsh_in_node_order_matches_the_callers_order(d, block, x64):
    """``eigsh`` on ``op.matvec`` runs in the operator's Morton order; on a
    bare callable of the same operator it runs in the caller's.  The
    results agree up to the order of floating-point sums."""
    with jax.enable_x64(x64):
        dtype = jnp.float64 if x64 else jnp.float32
        op = _adjacency(d, dtype)
        kw = dict(key=jax.random.PRNGKey(1), block_size=block, dtype=dtype)
        got = eigsh(op.matvec, op.n, 4, **kw)
        want = eigsh(lambda x: op.matvec(x), op.n, 4, **kw)
    assert got.num_matvecs == want.num_matvecs
    np.testing.assert_allclose(np.asarray(got.eigenvalues),
                               np.asarray(want.eigenvalues), rtol=1e-6)
    # each eigenvector up to its sign
    sign = np.sign(np.sum(np.asarray(got.eigenvectors)
                          * np.asarray(want.eigenvectors), axis=0))
    np.testing.assert_allclose(np.asarray(got.eigenvectors) * sign,
                               np.asarray(want.eigenvectors), rtol=0,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.residual_bounds),
                               np.asarray(want.residual_bounds), rtol=0,
                               atol=1e-5)
