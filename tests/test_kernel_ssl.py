"""Kernel SSL by CG on the normal path (``make_normalized_adjacency``, then
``kernel_ssl_cg``) against a dense float64 solve of ``(I + beta L_s) u =
f``: one binary column at d = 2, four one-vs-rest columns in lockstep at
d = 3 (paper Sec. 6.2.2-6.2.3, Eq. (6.4))."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FastsumParams, dense_normalized_adjacency,
                        make_kernel, make_normalized_adjacency)
from repro.data import crescent_fullmoon, synthetic_image
from repro.graph import kernel_ssl_cg, predicted_labels, training_matrix

BETA = 1e3  # the paper's, and the benchmark cells'


def given_labels(classes: np.ndarray, per_class: int, n_classes: int,
                 seed: int) -> np.ndarray:
    """``per_class`` labelled nodes of each class, -1 elsewhere."""
    rng = np.random.default_rng(seed)
    given = np.full(classes.shape, -1, np.int32)
    for c in range(n_classes):
        idx = rng.choice(np.flatnonzero(classes == c), per_class,
                         replace=False)
        given[idx] = c
    return given


def crescent():
    points, classes = crescent_fullmoon(1500, seed=4)
    return points, classes, 2, 0.75


def image():
    img, lab = synthetic_image(30, 40, seed=4)
    return img.reshape(-1, 3), lab.reshape(-1), 4, 90.0


# (problem, NFFT setup, tolerance on |u - u*| / |u*| per column).  The
# setups are finer than the cells' (N=128 m=3; N=16 m=2) so that the
# operator adds little: 4e-12 (d = 2) and 2e-9 (d = 3) relative error on a
# random vector, measured against the dense product.  The error of u is at
# most cond(I + beta L_s) ~ 1.2e3 (d = 2) and 1.0e3 (d = 3) times the CG
# tolerance 1e-10 plus the operator's error: 1.3e-7 and 2.2e-6, under the
# tolerances below.
CASES = [
    pytest.param(crescent, FastsumParams(n_bandwidth=256, m=6, eps_b=0.0),
                 1e-6, id="d2-one-column"),
    pytest.param(image, FastsumParams(n_bandwidth=32, m=5, p=5, eps_b=0.125),
                 1e-5, id="d3-four-columns"),
]


@pytest.mark.parametrize("problem,params,rtol", CASES)
def test_kernel_ssl_cg_matches_the_dense_solve(problem, params, rtol):
    points, classes, n_classes, sigma = problem()
    kernel = make_kernel("gaussian", sigma=sigma)
    pts = jnp.asarray(points)
    f = training_matrix(jnp.asarray(given_labels(classes, 5, n_classes, 1)),
                        n_classes)
    assert f.shape == ((pts.shape[0],) if n_classes == 2
                       else (pts.shape[0], n_classes))

    res = kernel_ssl_cg(make_normalized_adjacency(kernel, pts, params), f,
                        BETA, tol=1e-10, maxiter=1000)

    a = np.asarray(dense_normalized_adjacency(kernel, pts))
    eye = np.eye(a.shape[0])
    u_star = np.linalg.solve(eye + BETA * (eye - a), np.asarray(f))
    u = np.asarray(res.u)
    assert u.shape == u_star.shape
    err = (np.linalg.norm(u - u_star, axis=0)
           / np.linalg.norm(u_star, axis=0))
    assert np.all(err < rtol), err
    # one count per column, each column converged
    assert np.shape(res.num_iters) == np.shape(res.converged) == f.shape[1:]
    assert np.all(np.asarray(res.converged))
    assert np.all((np.asarray(res.num_iters) > 0)
                  & (np.asarray(res.num_iters) < 1000))
    np.testing.assert_array_equal(
        np.asarray(predicted_labels(res.u)),
        np.asarray(predicted_labels(jnp.asarray(u_star))))


def test_training_matrix_and_predicted_labels():
    given = jnp.asarray([-1, 0, 1, 2, -1, 1])
    np.testing.assert_array_equal(
        np.asarray(training_matrix(given, 3)),
        [[0, 0, 0], [1, -1, -1], [-1, 1, -1], [-1, -1, 1], [0, 0, 0],
         [-1, 1, -1]])
    binary = jnp.asarray([-1, 0, 1, 1])
    np.testing.assert_array_equal(np.asarray(training_matrix(binary, 2)),
                                  [0, -1, 1, 1])
    np.testing.assert_array_equal(
        np.asarray(predicted_labels(jnp.asarray([0.3, -0.1, 0.0]))),
        [1, 0, 0])
    np.testing.assert_array_equal(
        np.asarray(predicted_labels(jnp.asarray([[0.1, 0.4, -1.0],
                                                  [0.9, 0.2, 0.3]]))),
        [1, 0])


def small_crescent():
    points, classes = crescent_fullmoon(600, seed=5)
    return points, classes, 2, 0.75, FastsumParams(n_bandwidth=64, m=3,
                                                   eps_b=0.0)


def small_image():
    img, lab = synthetic_image(20, 30, seed=5)
    return (img.reshape(-1, 3), lab.reshape(-1), 4, 90.0,
            FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=0.125))


@pytest.mark.parametrize("problem", [small_crescent, small_image],
                         ids=["d2-one-column", "d3-four-columns"])
def test_kernel_ssl_cg_in_node_order_matches_the_callers_order(problem):
    """``kernel_ssl_cg`` composes its system from the adjacency's Morton
    order application, so its CG runs there (``f`` in and ``u`` out once);
    plain ``cg`` on the same system as a bare callable runs in the
    caller's order.  Same iterations per column, and the same ``u`` to the
    solver's tolerance."""
    from repro.core import cg

    points, classes, n_classes, sigma, params = problem()
    op = make_normalized_adjacency(make_kernel("gaussian", sigma=sigma),
                                   jnp.asarray(points), params)
    f = training_matrix(jnp.asarray(given_labels(classes, 5, n_classes, 2)),
                        n_classes)
    tol = 1e-8
    got = kernel_ssl_cg(op, f, BETA, tol=tol, maxiter=1000)
    want = cg(lambda x: x + BETA * op.laplacian_matvec(x), f, tol=tol,
              maxiter=1000)
    np.testing.assert_array_equal(np.asarray(got.num_iters),
                                  np.asarray(want.num_iters))
    assert np.all(np.asarray(got.converged))
    # both iterates meet the tolerance on the residual; cond(I + beta L_s)
    # <= 1 + 2 beta bounds their distance
    err = np.linalg.norm(np.asarray(got.u - want.x), axis=0)
    bound = 2 * (1 + 2 * BETA) * tol * np.linalg.norm(np.asarray(f), axis=0)
    assert np.all(err <= bound), (err, bound)
