"""CG / MINRES vs numpy direct solves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cg, minres


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(n, n))
    return jnp.asarray(m @ m.T + n * np.eye(n))


def test_cg_spd():
    a = _spd(120)
    b = jnp.asarray(np.random.default_rng(1).normal(size=120))
    sol = cg(lambda x: a @ x, b, tol=1e-12, maxiter=500)
    assert bool(sol.converged)
    ref = np.linalg.solve(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(sol.x), ref, rtol=1e-8, atol=1e-8)


def test_cg_preconditioned():
    a = _spd(120, seed=2)
    d = jnp.diag(a)
    b = jnp.asarray(np.random.default_rng(3).normal(size=120))
    sol_pc = cg(lambda x: a @ x, b, tol=1e-12, maxiter=500,
                preconditioner=lambda r: r / d)
    sol = cg(lambda x: a @ x, b, tol=1e-12, maxiter=500)
    assert bool(sol_pc.converged)
    np.testing.assert_allclose(np.asarray(sol_pc.x), np.asarray(sol.x),
                               rtol=1e-7, atol=1e-7)


def test_minres_spd_matches_cg():
    a = _spd(100, seed=4)
    b = jnp.asarray(np.random.default_rng(5).normal(size=100))
    s1 = cg(lambda x: a @ x, b, tol=1e-12, maxiter=500)
    s2 = minres(lambda x: a @ x, b, tol=1e-12, maxiter=500)
    np.testing.assert_allclose(np.asarray(s1.x), np.asarray(s2.x),
                               rtol=1e-7, atol=1e-7)


def _ill_conditioned_spd(n=150, decades=6, seed=1):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    vals = np.logspace(-decades, 0, n)
    return jnp.asarray(q @ np.diag(vals) @ q.T), \
        jnp.asarray(rng.normal(size=n))


def test_cg_reports_true_residual_on_ill_conditioned():
    """The recurrence residual drifts below the attainable accuracy on an
    ill-conditioned operator (cond ~1e6, tol below the final-accuracy
    limit): the recurrence used to claim ~1e-10 convergence while
    ||b - A x|| stagnates ~1e-9.  The exit recompute makes residual_norm
    and converged describe the returned iterate."""
    a, b = _ill_conditioned_spd()
    tol = 1e-11
    sol = cg(lambda x: a @ x, b, tol=tol, maxiter=20000)
    true_res = float(jnp.linalg.norm(b - a @ sol.x))
    assert abs(float(sol.residual_norm) - true_res) <= 1e-6 * true_res
    tol_abs = tol * max(float(jnp.linalg.norm(b)), 1.0)
    assert bool(sol.converged) == (true_res <= tol_abs)
    # the drift is real: the solve stalled above the requested tolerance
    assert true_res > tol_abs, (true_res, tol_abs)


def test_minres_reports_true_residual_on_ill_conditioned():
    """Same as the CG test; MINRES's |phi_bar| shrinks monotonically by
    construction (a product of Givens sines), so it is guaranteed to drift
    below the true residual — here by ~3 orders of magnitude."""
    a, b = _ill_conditioned_spd()
    tol = 1e-11
    sol = minres(lambda x: a @ x, b, tol=tol, maxiter=20000)
    true_res = float(jnp.linalg.norm(b - a @ sol.x))
    assert abs(float(sol.residual_norm) - true_res) <= 1e-6 * true_res
    tol_abs = tol * max(float(jnp.linalg.norm(b)), 1.0)
    assert bool(sol.converged) == (true_res <= tol_abs)
    assert true_res > tol_abs, (true_res, tol_abs)


def test_batched_per_column_convergence_wildly_different_scales():
    """Batched (n, C) solves keep independent per-column bookkeeping: with
    columns spanning 12 orders of magnitude, every column must satisfy its
    OWN tolerance ``tol * max(||b_c||, 1)``.  The old global-norm
    bookkeeping let the 1e6-scale column dominate the convergence test (the
    tiny columns stopped at absolute residuals far above their own
    tolerance) and coupled all columns through a single step size."""
    a = _spd(120, seed=7)
    scales = np.array([1e-6, 1.0, 1e6])
    b = jnp.asarray(np.random.default_rng(8).normal(size=(120, 3)) * scales)
    tol = 1e-10
    for solver in (cg, minres):
        sol = solver(lambda x: a @ x, b, tol=tol, maxiter=2000)
        assert sol.x.shape == (120, 3)
        assert sol.num_iters.shape == (3,)
        tol_abs = tol * np.maximum(
            np.linalg.norm(np.asarray(b), axis=0), 1.0)
        true_res = np.linalg.norm(
            np.asarray(b) - np.asarray(a) @ np.asarray(sol.x), axis=0)
        np.testing.assert_allclose(np.asarray(sol.residual_norm), true_res,
                                   rtol=1e-6)
        assert np.all(true_res <= tol_abs), (solver.__name__, true_res,
                                             tol_abs)
        assert bool(jnp.all(sol.converged))
        # columns converge at different iteration counts — the easy tiny
        # column froze early instead of riding along to the global stop
        assert int(sol.num_iters[0]) < int(sol.num_iters[2])


def test_maxiter_exhaustion_exit_reporting_mixed_scales():
    """When ``maxiter`` runs out with only SOME columns converged, the exit
    report must stay per-column consistent: ``residual_norm`` is the true
    recomputed ``||b_c - A x_c||``, ``converged`` is derived from it against
    the column's own tolerance, and ``num_iters`` shows which columns froze
    early vs. rode to the iteration cap.  Mixed per-column scales make the
    recurrence residuals drift by very different amounts, which is exactly
    where stale-recurrence reporting used to lie."""
    # ill-conditioned SPD: diag spectrum over 10 orders of magnitude
    n = 100
    rng = np.random.default_rng(12)
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    eig = np.logspace(-8, 2, n)
    a = jnp.asarray(q @ np.diag(eig) @ q.T)
    scales = np.array([1e-5, 1.0, 1e5])
    b = jnp.asarray(rng.normal(size=(n, 3)) * scales)
    tol = 1e-9
    maxiter = 25  # far too few for this conditioning
    for solver in (cg, minres):
        sol = solver(lambda x: a @ x, b, tol=tol, maxiter=maxiter)
        res = np.asarray(sol.residual_norm)
        iters = np.asarray(sol.num_iters)
        conv = np.asarray(sol.converged)
        # 1. residual_norm is the TRUE residual of the returned x, not the
        #    drifted recurrence scalar
        true_res = np.linalg.norm(
            np.asarray(b) - np.asarray(a) @ np.asarray(sol.x), axis=0)
        np.testing.assert_allclose(res, true_res, rtol=1e-6,
                                   err_msg=solver.__name__)
        # 2. converged agrees with the true residual per column, against
        #    that column's own tolerance
        tol_abs = tol * np.maximum(
            np.linalg.norm(np.asarray(b), axis=0), 1.0)
        np.testing.assert_array_equal(conv, true_res <= tol_abs,
                                      err_msg=solver.__name__)
        # 3. the cap was genuinely hit — this test exercises the exhaustion
        #    path, not ordinary convergence
        assert not conv.all(), (solver.__name__, res, tol_abs)
        assert iters.max() == maxiter, (solver.__name__, iters)
        # 4. num_iters is per-column: an unconverged column reports the full
        #    cap; a converged one reports where it froze
        assert np.all(iters[~conv] == maxiter), (solver.__name__, iters)
        assert np.all(iters <= maxiter)
        # 5. the returned x is still the best-so-far iterate, finite
        assert np.all(np.isfinite(np.asarray(sol.x)))


def test_batched_columns_match_independent_solves():
    """Each column of a lockstep batched solve equals its own 1-D solve."""
    a = _spd(100, seed=9)
    b = jnp.asarray(np.random.default_rng(10).normal(size=(100, 4)))
    for solver in (cg, minres):
        batched = solver(lambda x: a @ x, b, tol=1e-12, maxiter=1000)
        for c in range(4):
            single = solver(lambda x: a @ x, b[:, c], tol=1e-12,
                            maxiter=1000)
            np.testing.assert_allclose(np.asarray(batched.x[:, c]),
                                       np.asarray(single.x),
                                       rtol=1e-8, atol=1e-8)


def test_cg_complex_hpd():
    """The per-column rewrite must keep complex Hermitian-positive-definite
    operators working (conjugating inner products, modulus norms)."""
    rng = np.random.default_rng(11)
    m = rng.normal(size=(60, 60)) + 1j * rng.normal(size=(60, 60))
    a = jnp.asarray(m @ m.conj().T + 60 * np.eye(60))
    b = jnp.asarray(rng.normal(size=60) + 1j * rng.normal(size=60))
    sol = cg(lambda x: a @ x, b, tol=1e-12, maxiter=500)
    assert bool(sol.converged)
    ref = np.linalg.solve(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(sol.x), ref, rtol=1e-8, atol=1e-8)


def test_minres_indefinite():
    rng = np.random.default_rng(6)
    n = 100
    m = rng.normal(size=(n, n))
    a = jnp.asarray((m + m.T) / 2.0 + 0.5 * np.eye(n))  # symmetric indefinite
    b = jnp.asarray(rng.normal(size=n))
    sol = minres(lambda x: a @ x, b, tol=1e-10, maxiter=2000)
    ref = np.linalg.solve(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(sol.x), ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Guarded execution (PR 7): non-finite rhs, quarantine, stagnation
# ---------------------------------------------------------------------------

def test_nonfinite_rhs_returns_immediately():
    """Regression: a NaN/Inf rhs used to iterate to maxiter (every norm
    comparison with NaN is False, so the active mask never cleared).  The
    up-front validation must return at once with converged=False and the
    per-column rhs_nonfinite flag set."""
    a = _spd(60, seed=20)
    for bad in (np.nan, np.inf):
        b = jnp.asarray(np.full((60,), bad))
        for solver in (cg, minres):
            sol = solver(lambda x: a @ x, b, tol=1e-10, maxiter=5000)
            assert int(sol.num_iters) == 0
            assert not bool(sol.converged)
            assert bool(sol.health.rhs_nonfinite)
            assert not np.isfinite(float(sol.residual_norm))
            assert np.all(np.isfinite(np.asarray(sol.x)))


def test_nonfinite_rhs_column_isolated_in_batch():
    """One poisoned rhs column must not affect its lockstep siblings."""
    a = _spd(80, seed=21)
    rng = np.random.default_rng(22)
    b = rng.normal(size=(80, 3))
    b[:, 1] = np.nan
    bj = jnp.asarray(b)
    for solver in (cg, minres):
        sol = solver(lambda x: a @ x, bj, tol=1e-12, maxiter=1000)
        health = sol.health
        assert list(np.asarray(health.rhs_nonfinite)) == [False, True, False]
        for c in (0, 2):
            ref = np.linalg.solve(np.asarray(a), b[:, c])
            np.testing.assert_allclose(np.asarray(sol.x[:, c]), ref,
                                       rtol=1e-8, atol=1e-8)
        assert np.all(np.asarray(sol.x[:, 1]) == 0.0)
        assert np.all(np.isfinite(np.asarray(sol.x)))


def test_healthy_solves_report_clean_health():
    a = _spd(50, seed=23)
    b = jnp.asarray(np.random.default_rng(24).normal(size=(50, 2)))
    for solver in (cg, minres):
        sol = solver(lambda x: a @ x, b, tol=1e-12, maxiter=500)
        assert np.all(np.asarray(sol.converged))
        h = sol.health
        assert not np.any(np.asarray(h.any_fault))
        assert np.all(np.asarray(h.breakdown_iter) == -1)


class _ShiftedAdjacency:
    """``I + beta (I - A)`` on a fastsum adjacency: SPD, and with ``A``'s
    node order (``repro.core.node_order``), so ``cg`` on its ``matvec``
    runs in Morton order."""

    def __init__(self, op, beta):
        self.op, self.beta = op, beta

    def matvec(self, x):
        return x + self.beta * (x - self.op.matvec(x))

    def node_order(self):
        order = self.op.node_order()
        return order._replace(
            matvec=lambda x: x + self.beta * (x - order.matvec(x)))


def _shifted(d: int, beta=10.0):
    from repro.core import (FastsumParams, make_kernel,
                            make_normalized_adjacency)

    pts = jax.random.uniform(jax.random.PRNGKey(10 + d), (400, d))
    op = make_normalized_adjacency(make_kernel("gaussian", sigma=0.3), pts,
                                   FastsumParams(n_bandwidth=16, m=3))
    return _ShiftedAdjacency(op, beta)


@pytest.mark.parametrize("columns,with_x0", [(None, False), (3, True)],
                         ids=["one-column", "three-columns-x0"])
@pytest.mark.parametrize("implicit_diff", [False, True],
                         ids=["plain", "implicit-diff"])
@pytest.mark.parametrize("d", [2, 3])
def test_cg_in_node_order_matches_the_callers_order(d, implicit_diff,
                                                    columns, with_x0):
    """``cg`` on the bound ``matvec`` of an operator with a node order runs
    in that order, ``b``/``x0`` in and ``x`` out once; on a bare callable
    it runs in the caller's.  Same iterations, same solution to the
    tolerance."""
    system = _shifted(d)
    rng = np.random.default_rng(d)
    shape = (400,) if columns is None else (400, columns)
    b = jnp.asarray(rng.normal(size=shape))
    x0 = jnp.asarray(0.1 * rng.normal(size=shape)) if with_x0 else None
    kw = dict(x0=x0, tol=1e-8, maxiter=200, implicit_diff=implicit_diff)
    got = cg(system.matvec, b, **kw)
    want = cg(lambda x: system.matvec(x), b, **kw)
    np.testing.assert_array_equal(np.asarray(got.num_iters),
                                  np.asarray(want.num_iters))
    assert np.all(np.asarray(got.converged))
    np.testing.assert_allclose(np.asarray(got.residual_norm),
                               np.asarray(want.residual_norm), rtol=1e-3)
    err = np.linalg.norm(np.asarray(got.x - want.x), axis=0)
    assert np.all(err <= 1e-8 * np.linalg.norm(np.asarray(b), axis=0)), err


def test_cg_with_a_preconditioner_keeps_the_callers_order():
    """A preconditioner works in the caller's order, so the solve does
    too, and agrees with the unpreconditioned solve in node order."""
    system = _shifted(2)
    b = jnp.asarray(np.random.default_rng(5).normal(size=400))
    got = cg(system.matvec, b, tol=1e-10, maxiter=200,
             preconditioner=lambda r: r / (1.0 + system.beta))
    want = cg(system.matvec, b, tol=1e-10, maxiter=200)
    assert bool(got.converged) and bool(want.converged)
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                               rtol=0, atol=1e-8)


@pytest.mark.parametrize("d", [2, 3])
def test_implicit_diff_cg_in_node_order_gradcheck(d):
    """Gradients through an implicit-diff CG that runs in node order: with
    respect to ``b`` they equal the caller's-order solve's, and with
    respect to ``beta`` (closed over by the operator) central differences."""
    op = _shifted(d).op
    rng = np.random.default_rng(40 + d)
    b = jnp.asarray(rng.normal(size=400))
    w = jnp.asarray(rng.normal(size=400))

    def loss(beta, rhs, bare=False):
        system = _ShiftedAdjacency(op, beta)
        mv = (lambda x: system.matvec(x)) if bare else system.matvec
        return jnp.vdot(w, cg(mv, rhs, tol=1e-12, maxiter=400).x)

    g_b = jax.grad(loss, argnums=1)(10.0, b)
    g_b_bare = jax.grad(loss, argnums=1)(10.0, b, bare=True)
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_b_bare),
                               rtol=0, atol=1e-9 * float(jnp.max(
                                   jnp.abs(g_b_bare))))
    g_beta = float(jax.grad(loss)(10.0, b))
    h = 1e-4
    fd = float(loss(10.0 + h, b) - loss(10.0 - h, b)) / (2 * h)
    assert abs(g_beta - fd) <= 1e-6 * max(abs(fd), 1e-12), (g_beta, fd)
