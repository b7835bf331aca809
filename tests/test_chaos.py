"""Fault-injection (chaos) suite: seeded failures through solve + serve.

Every test drives the stack through a :mod:`repro.runtime.faultinject`
injector and asserts three things: the fault is *detected* (health flags /
counters / request errors), its blast radius is *contained* (siblings,
other tenants, and later traffic are unaffected), and the system
*recovers* (clean state is rebuilt from the source of truth).  Correct
outputs are always asserted against dense oracles — a guard that silently
serves wrong values is worse than no guard.

Marked ``chaos``: CI runs this file as its own job (``pytest -m chaos``);
it also rides the default tier-1 run.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FastsumParams, cg, cg_bank, eigsh, fused_spectral_multiplier,
    make_fastsum, make_kernel, minres,
)
from repro.core import fastsum_exec
from repro.graph import krr_fit, krr_predict_direct
from repro.runtime import (
    TickChaos, corrupt_group_plan, poison_bank_member, poison_columns,
    poison_registry_grids,
)
from repro.serving import GraphModelRegistry, GraphServeEngine, PredictRequest

pytestmark = pytest.mark.chaos

PARAMS = FastsumParams(n_bandwidth=64, m=4)
TOL = 1e-3  # NFFT prediction error at these settings is ~1e-4


# ---------------------------------------------------------------------------
# Solver-side chaos
# ---------------------------------------------------------------------------

def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(n, n))
    return jnp.asarray(m @ m.T + n * np.eye(n))


@pytest.mark.parametrize("solver", [cg, minres])
def test_poisoned_column_quarantined_not_contagious(solver):
    """A per-column NaN operator fault must quarantine exactly that column
    (health.nonfinite) while lockstep siblings converge to the oracle."""
    a = _spd(80, seed=1)
    mv = poison_columns(lambda x: a @ x, [1])
    b = np.random.default_rng(2).normal(size=(80, 3))
    sol = solver(mv, jnp.asarray(b), tol=1e-10, maxiter=2000)
    h = sol.health
    assert list(np.asarray(h.nonfinite)) == [False, True, False]
    assert not np.any(np.asarray(h.rhs_nonfinite))
    assert int(np.asarray(h.breakdown_iter)[1]) == 0  # caught immediately
    for c in (0, 2):
        assert bool(np.asarray(sol.converged)[c])
        ref = np.linalg.solve(np.asarray(a), b[:, c])
        np.testing.assert_allclose(np.asarray(sol.x[:, c]), ref,
                                   rtol=1e-7, atol=1e-7)
    # the poisoned column froze at its (finite) initial state
    assert not bool(np.asarray(sol.converged)[1])
    assert np.all(np.isfinite(np.asarray(sol.x)))


def test_poisoned_bank_member_isolated_in_bank_solve():
    """One bad tenant's operator in a lockstep bank sweep: all its columns
    quarantined, sibling *systems* untouched."""
    mats = [_spd(50, seed=s) for s in (3, 4, 5)]
    stack = jnp.stack(mats)

    def bank_mv(xb):  # (S, n, C) -> (S, n, C)
        return jnp.einsum("sij,sjc->sic", stack, xb)

    mv = poison_bank_member(bank_mv, [1])
    b = np.random.default_rng(6).normal(size=(3, 50, 2))
    sol = cg_bank(mv, jnp.asarray(b), tol=1e-10, maxiter=2000)
    h = sol.health
    assert h.nonfinite.shape == (3, 2)
    assert np.all(np.asarray(h.nonfinite)[1])
    assert not np.any(np.asarray(h.nonfinite)[[0, 2]])
    for s in (0, 2):
        for c in range(2):
            ref = np.linalg.solve(np.asarray(mats[s]), b[s, :, c])
            np.testing.assert_allclose(np.asarray(sol.x[s, :, c]), ref,
                                       rtol=1e-7, atol=1e-7)
    assert np.all(np.isfinite(np.asarray(sol.x)))


def test_eigsh_poisoned_operator_flagged_not_trusted():
    """A fully poisoned operator: eigsh returns finite sentinel values but
    flags health.nonfinite with inf residual bounds — detectably broken,
    never NaN-silent."""
    res = eigsh(lambda x: jnp.full_like(x, jnp.nan), n=40, k=3,
                num_iters=20)
    assert bool(np.asarray(res.health.nonfinite))
    assert int(np.asarray(res.health.breakdown_iter)) == 0
    assert np.all(np.isinf(np.asarray(res.residual_bounds)))
    assert np.all(np.isfinite(np.asarray(res.eigenvalues)))


def test_grid_hook_is_the_fault_seam():
    """The spread grid's seam is ``fused_pipeline(spectral_op=...)``: an op
    that runs the normal mid-section (rfftn -> multiply -> irfftn) changes
    nothing, bit for bit; a poisoning op propagates NaN to every output
    (which the serving guard then catches)."""
    rng = np.random.default_rng(8)
    pts = jnp.asarray(rng.normal(size=(100, 2)))
    fs = make_fastsum(make_kernel("gaussian", sigma=3.5), pts,
                      FastsumParams(n_bandwidth=16, m=4))
    plan = fs.plan
    mult = fused_spectral_multiplier(plan, fs.b_hat)
    x = jnp.asarray(rng.normal(size=(100,)))
    axes = tuple(range(plan.d))

    def mid(g, bank):
        g_hat = jnp.fft.rfftn(g, axes=axes) * bank[0][..., None]
        return jnp.fft.irfftn(g_hat, s=(plan.grid_size,) * plan.d,
                              axes=axes)

    base = fastsum_exec.fused_pipeline(plan, mult, fs.src_window,
                                       fs.src_window, x)
    same = fastsum_exec.fused_pipeline(plan, mult, fs.src_window,
                                       fs.src_window, x, spectral_op=mid)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
    bad = fastsum_exec.fused_pipeline(
        plan, mult, fs.src_window, fs.src_window, x,
        spectral_op=lambda g, bank: jnp.full_like(g, jnp.nan))
    assert not np.any(np.isfinite(np.asarray(bad)))


# ---------------------------------------------------------------------------
# Serving-side chaos
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def models():
    rng = np.random.default_rng(11)
    xtr = jnp.asarray(rng.uniform(-3, 3, (300, 2)))
    ytr = jnp.asarray(np.sign(rng.standard_normal(300)))
    m_a = krr_fit(make_kernel("gaussian", sigma=1.0), xtr, ytr, 1e-2, PARAMS)
    m_b = krr_fit(make_kernel("gaussian", sigma=1.5), xtr, ytr, 1e-2, PARAMS)
    return {"a": m_a, "b": m_b}


@pytest.fixture()
def registry(models):
    reg = GraphModelRegistry()
    for mid, model in models.items():
        reg.register(mid, model)
    return reg


def _submit(engine, uid, mid, q, rhs=None, deadline_s=None):
    req = PredictRequest(uid=uid, model_id=mid, query_points=np.asarray(q),
                         rhs=None if rhs is None else np.asarray(rhs),
                         deadline_s=deadline_s)
    engine.submit(req)
    return req


def _oracle(models, mid, q):
    return np.asarray(krr_predict_direct(models[mid], jnp.asarray(q)))


def test_poisoned_grids_evict_trip_breaker_and_recover(models, registry):
    """NaN-poisoned cached grids: affected requests fail with the
    non-finite guard (never serve NaN), the tenant's breaker trips and
    invalidates its grids, the circuit sheds load during cooldown, and
    post-cooldown traffic is served correctly from rebuilt grids.  The
    sibling tenant sharing the group is never affected."""
    rng = np.random.default_rng(20)
    engine = GraphServeEngine(registry, slots=2, chunk=16,
                              breaker_threshold=2, breaker_cooldown=2)
    # warm: build the (a, alpha) grid
    warm = _submit(engine, 0, "a", rng.uniform(-2, 2, (8, 2)))
    engine.run_until_drained()
    assert warm.done and warm.error is None

    assert poison_registry_grids(registry, "a", frac=0.5, seed=1) == 1
    r1 = _submit(engine, 1, "a", rng.uniform(-2, 2, (8, 2)))
    r2 = _submit(engine, 2, "a", rng.uniform(-2, 2, (8, 2)))
    stats = engine.step()
    assert r1.done and "non-finite" in r1.error
    assert r2.done and "non-finite" in r2.error
    assert stats.nonfinite == 2
    assert engine.counters["nonfinite"] == 2
    assert engine.counters["breaker_trips"] == 1
    assert registry.counters["grid_invalidations"] >= 1

    # circuit open: tenant "a" load is shed at admission …
    r3 = _submit(engine, 3, "a", rng.uniform(-2, 2, (8, 2)))
    engine.step()
    assert r3.done and "circuit open" in r3.error
    assert engine.counters["breaker_rejections"] == 1
    # … while the sibling tenant in the SAME group keeps being served
    qb = rng.uniform(-2, 2, (10, 2))
    rb = _submit(engine, 4, "b", qb)
    engine.run_until_drained()
    assert rb.done and rb.error is None
    np.testing.assert_allclose(rb.output, _oracle(models, "b", qb),
                               atol=TOL)

    # past the cooldown: clean grids rebuilt from the registered alpha
    for _ in range(4):
        engine.step()
    qa = rng.uniform(-2, 2, (12, 2))
    r5 = _submit(engine, 5, "a", qa)
    engine.run_until_drained()
    assert r5.done and r5.error is None, r5.error
    np.testing.assert_allclose(r5.output, _oracle(models, "a", qa),
                               atol=TOL)


def test_corrupted_plan_detected_rebuilt_and_served(models, registry):
    """A corrupted frozen PredictionPlan makes in-domain queries look
    inadmissible; the engine must detect the violated plan invariant,
    rebuild the tenant group from its registered models, and serve the
    request correctly in the same admission."""
    rng = np.random.default_rng(21)
    engine = GraphServeEngine(registry, slots=2, chunk=16)
    assert corrupt_group_plan(registry, "a")
    qa = rng.uniform(-2, 2, (10, 2))
    qb = rng.uniform(-2, 2, (10, 2))
    ra = _submit(engine, 0, "a", qa)
    rb = _submit(engine, 1, "b", qb)  # same group: rides the same rebuild
    engine.run_until_drained()
    assert ra.done and ra.error is None, ra.error
    assert rb.done and rb.error is None, rb.error
    np.testing.assert_allclose(ra.output, _oracle(models, "a", qa),
                               atol=TOL)
    np.testing.assert_allclose(rb.output, _oracle(models, "b", qb),
                               atol=TOL)
    assert engine.counters["plan_rebuilds"] == 1
    assert registry.counters["group_rebuilds"] == 1
    assert any(t.rebuilds for t in engine.tick_log)


def test_deadline_expiry_evicts_and_recycles_slot(models, registry):
    """An in-flight request whose deadline passes is evicted with its slot
    recycled the same tick; queued requests with expired deadlines never
    occupy a slot at all."""
    rng = np.random.default_rng(22)
    engine = GraphServeEngine(registry, slots=1, chunk=4)
    long = _submit(engine, 0, "a", rng.uniform(-2, 2, (64, 2)),
                   deadline_s=3600.0)
    engine.step()
    assert not long.done  # mid-flight (64 rows at chunk 4)
    long.submitted_at -= 7200.0  # deterministically expire the deadline
    qn = rng.uniform(-2, 2, (6, 2))
    nxt = _submit(engine, 1, "a", qn)
    stats = engine.step()
    assert long.done and "deadline" in long.error
    assert stats.evicted == 1
    # the freed slot admitted the next request in the SAME tick
    assert stats.occupancy == 1 and stats.rows > 0
    engine.run_until_drained()
    assert nxt.done and nxt.error is None
    np.testing.assert_allclose(nxt.output, _oracle(models, "a", qn),
                               atol=TOL)
    # queued-expiry path: deadline already passed when admission runs
    dead = _submit(engine, 2, "a", rng.uniform(-2, 2, (4, 2)),
                   deadline_s=1e-9)
    engine.step()
    assert dead.done and "deadline" in dead.error
    assert engine.counters["deadline_evicted"] == 2


def test_out_of_domain_rejected_or_replanned_never_wrong(models, registry):
    """Out-of-domain queries would wrap the NFFT torus into silently wrong
    values.  reject mode fails them; replan mode serves them through the
    exact slow path — asserted against the dense oracle."""
    # just past the registered domain (train ∪ margin): far enough to be
    # inadmissible, near enough that the replan's joint rescaling keeps the
    # NFFT error well under TOL and the oracle values are meaningfully
    # nonzero (a zeros-vs-zeros comparison would prove nothing)
    q_out = np.array([[4.5, -4.0], [5.0, 5.0], [4.2, 0.0]])
    rej = GraphServeEngine(registry, slots=2, chunk=8,
                           out_of_domain="reject")
    r = _submit(rej, 0, "a", q_out)
    rej.step()
    assert r.done and "domain" in r.error and r.output is None
    assert rej.counters["out_of_domain"] == 1
    assert any(t.out_of_domain for t in rej.tick_log)

    rep = GraphServeEngine(registry, slots=2, chunk=8,
                           out_of_domain="replan")
    r2 = _submit(rep, 1, "a", q_out)
    rep.step()
    assert r2.done and r2.error is None
    np.testing.assert_allclose(r2.output, _oracle(models, "a", q_out),
                               atol=TOL)
    assert rep.counters["replans"] == 1
    # non-finite queries are rejected even in replan mode
    r3 = _submit(rep, 2, "a", np.full((3, 2), np.nan))
    rep.step()
    assert r3.done and "non-finite query" in r3.error


def test_dropped_ticks_delay_but_never_corrupt(models, registry):
    """Dropped ticks (injected at the chaos hook) stall progress for that
    tick only; every request still completes with oracle-correct output
    and the drops are counted."""
    rng = np.random.default_rng(23)
    chaos = TickChaos(drop_ticks=frozenset({0, 2}))
    engine = GraphServeEngine(registry, slots=2, chunk=4, chaos=chaos)
    qs = [rng.uniform(-2, 2, (10, 2)) for _ in range(3)]
    reqs = [_submit(engine, i, "a", q) for i, q in enumerate(qs)]
    engine.run_until_drained()
    assert engine.counters["dropped_ticks"] == 2
    assert sum(t.dropped for t in engine.tick_log) == 2
    for req, q in zip(reqs, qs):
        assert req.done and req.error is None
        np.testing.assert_allclose(req.output, _oracle(models, "a", q),
                                   atol=TOL)


def test_bounded_queue_backpressure(registry):
    """submit() rejects instead of growing the queue without bound."""
    rng = np.random.default_rng(24)
    engine = GraphServeEngine(registry, slots=1, chunk=8, max_queue=2)
    ok1 = engine.submit(PredictRequest(
        uid=0, model_id="a", query_points=rng.uniform(-2, 2, (4, 2))))
    ok2 = engine.submit(PredictRequest(
        uid=1, model_id="a", query_points=rng.uniform(-2, 2, (4, 2))))
    shed = PredictRequest(uid=2, model_id="a",
                          query_points=rng.uniform(-2, 2, (4, 2)))
    ok3 = engine.submit(shed)
    assert ok1 and ok2 and not ok3
    assert shed.done and "backpressure" in shed.error
    assert engine.counters["backpressure"] == 1
    engine.run_until_drained()  # the admitted two still drain fine
    assert engine.counters["finished"] == 2


def test_chaos_schedule_is_deterministic():
    from repro.runtime import chaos_schedule
    a = chaos_schedule(5, ticks=200, p_drop=0.1, p_slow=0.1)
    b = chaos_schedule(5, ticks=200, p_drop=0.1, p_slow=0.1)
    assert a.drop_ticks == b.drop_ticks
    assert a.slow_ticks == b.slow_ticks
    assert a.drop_ticks  # 200 ticks at p=0.1: some drops scheduled
