"""Krylov linear solvers driven by fast matvecs (paper Sections 4, 6.2.3, 6.3).

Conjugate Gradients (Hestenes–Stiefel) and MINRES (Paige–Saunders), both
matrix-free and jit-compatible (``lax.while_loop``).  Used for

    (I + beta L_s) u = f        (kernel SSL, Eq. 6.4)
    (K + beta I) alpha = f      (kernel ridge regression, Section 6.3)

with the matvec supplied by Algorithm 3.1/3.2 operators.

Batched right-hand sides ``b`` of shape (n, C) run C *independent*
recurrences in lockstep: per-column step sizes, per-column tolerances
(``tol * max(||b_c||, 1)``), and per-column convergence masks that freeze a
column's iterate once it converges while the others continue — one easy
column can no longer mask (or distort, through a shared global step size)
the convergence of the others.  The matvec is still invoked once per
iteration on the whole (n, C) block, so the fused fastsum engine amortizes
its spread/FFT/gather over all active systems.

``cg_bank`` / ``minres_bank`` lift the same lockstep machinery over a
*bank* axis: ``b`` of shape (S, n) or (S, n, C) with a bank matvec
``(S, n, C) -> (S, n, C)`` (e.g. ``FastsumOperatorBank.matvec``'s lockstep
flavor) solves all S·C systems with ONE bank matvec per iteration — the
execution shape of a hyperparameter sweep.

All solvers recompute the true residual ``||b - A x||`` (per column) at
exit: the recurrence residual drifts on ill-conditioned operators, so the
reported ``residual_norm`` / ``converged`` always describe the returned
iterate.

Guarded execution (``repro.runtime``): every solve also reports a
:class:`SolveHealth`.  A non-finite right-hand-side column is quarantined
*before* the loop (the solve returns immediately for it instead of
spinning to ``maxiter`` on NaNs); a column whose iterate goes non-finite
mid-solve — e.g. a poisoned operator member in a bank — is reverted to its
last finite iterate and frozen via the same per-column masks that freeze
converged columns, so one bad system can neither hang nor pollute its
lockstep siblings; a column whose residual stops improving for
``stall_window`` consecutive iterations is frozen as stagnated (Krylov
breakdown under inexact matvecs — the attainable-accuracy wall — no longer
burns the full ``maxiter`` budget).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import node_order, scopes

Array = jax.Array
Matvec = Callable[[Array], Array]

# A column "improves" only when its residual beats its best-so-far by this
# relative margin; anything smaller feeds the stagnation counter.  Cumulative
# over the window, so a legitimately (if slowly) converging column resets
# the counter long before a default window expires.
_STALL_RTOL = 1e-3


class SolveHealth(NamedTuple):
    """Per-column solver guard flags (shapes mirror ``converged``).

    ``rhs_nonfinite``
        the right-hand side (or ``x0``) held NaN/Inf; the column was
        quarantined before the first iteration and ``x`` is 0 for it.
    ``nonfinite``
        the iterate went non-finite mid-solve (poisoned operator,
        breakdown); ``x`` is the last finite iterate.
    ``stagnated``
        the residual stopped improving for ``stall_window`` iterations.
    ``breakdown_iter``
        iteration index at which ``nonfinite`` tripped, -1 if never.
    """

    rhs_nonfinite: Array
    nonfinite: Array
    stagnated: Array
    breakdown_iter: Array

    @property
    def any_fault(self) -> Array:
        return self.rhs_nonfinite | self.nonfinite | self.stagnated


class SolveResult(NamedTuple):
    x: Array
    num_iters: Array
    residual_norm: Array
    converged: Array
    health: SolveHealth | None = None


def _col_norms(v: Array) -> Array:
    """Per-column 2-norms of (n, C) -> (C,); complex-safe (|v|^2)."""
    return jnp.sqrt(jnp.sum(jnp.real(v * jnp.conj(v)), axis=0))


def _col_dot(u: Array, v: Array) -> Array:
    """Per-column <u, v> (conjugating, real part) of (n, C) -> (C,).

    The column-wise analogue of ``jnp.vdot(u, v).real`` — keeps the
    complex-HPD case working; for real dtypes XLA folds conj/real away.
    """
    return jnp.real(jnp.sum(jnp.conj(u) * v, axis=0))


def _as_columns(matvec: Matvec, b: Array, x0: Array | None,
                preconditioner: Matvec | None):
    """Normalize a (n,)- or (n, C)-shaped solve to the (n, C) layout."""
    batched = b.ndim == 2
    if batched:
        return matvec, b, x0, preconditioner, True
    mv = lambda u: matvec(u[:, 0])[:, None]
    pc = None if preconditioner is None \
        else (lambda u: preconditioner(u[:, 0])[:, None])
    return mv, b[:, None], None if x0 is None else x0[:, None], pc, False


def _squeeze_result(res: SolveResult, batched: bool) -> SolveResult:
    if batched:
        return res
    health = None if res.health is None else \
        SolveHealth(*(f[0] for f in res.health))
    return SolveResult(x=res.x[:, 0], num_iters=res.num_iters[0],
                       residual_norm=res.residual_norm[0],
                       converged=res.converged[0], health=health)


def _validate_rhs(b: Array, x0: Array | None):
    """Quarantine non-finite rhs / x0 columns before the loop.

    Returns ``(rhs_bad (C,), b_safe, x0_safe)`` — bad columns get a zero
    rhs (and zero start), so their residual is 0 from iteration 0 and they
    never enter the active set: an all-NaN ``b`` exits immediately with
    ``num_iters == 0`` instead of spinning to ``maxiter``.
    """
    rhs_bad = ~jnp.all(jnp.isfinite(b), axis=0)  # (C,)
    if x0 is not None:
        rhs_bad = rhs_bad | ~jnp.all(jnp.isfinite(x0), axis=0)
        x0 = jnp.where(rhs_bad[None, :], 0.0, x0)
    b_safe = jnp.where(rhs_bad[None, :], 0.0, b)
    return rhs_bad, b_safe, x0


def _finish(matvec: Matvec, b_safe: Array, x: Array, tol_abs: Array,
            iters: Array, rhs_bad: Array, poisoned: Array, stalled: Array,
            bad_iter: Array, batched: bool) -> SolveResult:
    """Shared exit path: true residual + health assembly.

    The recurrence residual drifts from ``b - A x`` on ill-conditioned
    operators (finite-precision rounding breaks the exact update
    invariant), so one extra matvec recomputes the true residual at exit —
    ``residual_norm`` / ``converged`` always describe the returned iterate.
    Quarantined-rhs columns report ``inf`` (deterministic, not NaN).
    """
    res = _col_norms(b_safe - matvec(x))
    # a poisoned operator column emits NaN even on the reverted (finite)
    # iterate; normalize any non-finite exit residual to inf so downstream
    # comparisons are deterministic
    res = jnp.where(rhs_bad | ~jnp.isfinite(res), jnp.inf, res)
    health = SolveHealth(rhs_nonfinite=rhs_bad, nonfinite=poisoned,
                         stagnated=stalled, breakdown_iter=bad_iter)
    return _squeeze_result(
        SolveResult(x=x, num_iters=iters, residual_norm=res,
                    converged=res <= tol_abs, health=health), batched)


def cg(matvec: Matvec, b: Array, *, x0: Array | None = None,
       tol: float = 1e-8, maxiter: int = 1000,
       preconditioner: Matvec | None = None,
       stall_window: int = 250, implicit_diff: bool = True) -> SolveResult:
    """Preconditioned conjugate gradients for SPD operators.

    ``b`` (n,): scalar recurrence, scalar result fields.  ``b`` (n, C):
    per-column recurrences in lockstep (see module docstring); ``x``
    (n, C) and ``num_iters`` / ``residual_norm`` / ``converged`` (C,).

    ``stall_window`` > 0 freezes a column whose residual fails to improve
    (by a relative ``1e-3``) for that many consecutive iterations; 0
    disables stagnation detection.  Guard flags land in ``result.health``.

    With ``implicit_diff=True`` (the default) the solve is differentiable
    by the implicit function theorem instead of by unrolling the Krylov
    loop: for ``A x* = b`` with symmetric ``A``, the backward pass solves
    ``A w = x̄`` — one more CG on the *same* operator (same tolerance,
    preconditioner, and guard machinery) — giving ``b̄ = w`` and, for any
    operator parameters θ captured by the ``matvec`` closure,
    ``θ̄ = −∂θ⟨w, A(θ) x*⟩``.  Closed-over tracers are hoisted out of the
    closure via ``jax.closure_convert``, so gradients reach spectral
    multipliers / kernel parameters inside a fastsum matvec transparently.
    Only ``x`` is differentiable; the diagnostics (``residual_norm``,
    ``num_iters``, ``converged``, ``health``) are treated as
    non-differentiable outputs.  Quarantined columns (``health.any_fault``)
    propagate exactly zero cotangents — a faulted solve never emits NaN
    gradients.  ``implicit_diff=False`` restores the plain forward-only
    recurrence (matvecs that refuse abstract tracing also fall back to it
    automatically).

    Handed the bound ``matvec`` of an operator with a node order (a fastsum
    adjacency, :mod:`repro.core.node_order`) and no preconditioner, the
    solve runs in that order: ``b`` and ``x0`` are permuted in once and
    ``x`` out once, and no application permutes.  The results are the same
    up to the order of floating-point sums.  A preconditioner works in the
    caller's order, so with one the solve does too.
    """
    order = None if preconditioner is not None else node_order.of(matvec)
    if order is not None:
        with jax.named_scope(scopes.KRYLOV):
            b, x0 = node_order.rows(order, b, x0)
        sol = cg(order.matvec, b, x0=x0, tol=tol, maxiter=maxiter,
                 stall_window=stall_window, implicit_diff=implicit_diff)
        with jax.named_scope(scopes.KRYLOV):
            return sol._replace(x=node_order.nodes(order, sol.x))
    if implicit_diff:
        conv = _try_closure_convert(matvec, b, preconditioner)
        if conv is not None:
            mv_c, mv_args, pc_c, pc_args = conv
            return _cg_implicit(mv_c, pc_c, (tol, maxiter, stall_window),
                                b, x0, mv_args, pc_args)
    return _cg_plain(matvec, b, x0=x0, tol=tol, maxiter=maxiter,
                     preconditioner=preconditioner,
                     stall_window=stall_window)


def _try_closure_convert(matvec, b, preconditioner):
    """Hoist closed-over jax values out of the matvec/preconditioner.

    Returns ``(mv_c, mv_args, pc_c, pc_args)`` or None when the callables
    cannot be abstractly traced (host callbacks, shape-dependent Python
    control flow) — the caller then degrades to the forward-only solver.
    """
    example = jnp.zeros(b.shape, b.dtype)
    try:
        mv_c, mv_args = jax.closure_convert(matvec, example)
        if preconditioner is None:
            pc_c, pc_args = None, []
        else:
            pc_c, pc_args = jax.closure_convert(preconditioner, example)
        return mv_c, tuple(mv_args), pc_c, tuple(pc_args)
    except Exception:
        return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _cg_implicit(mv_c, pc_c, statics, b, x0, mv_args, pc_args):
    tol, maxiter, stall_window = statics
    mv = lambda v: mv_c(v, *mv_args)
    pc = None if pc_c is None else (lambda v: pc_c(v, *pc_args))
    return _cg_plain(mv, b, x0=x0, tol=tol, maxiter=maxiter,
                     preconditioner=pc, stall_window=stall_window)


def _cg_implicit_fwd(mv_c, pc_c, statics, b, x0, mv_args, pc_args):
    sol = _cg_implicit(mv_c, pc_c, statics, b, x0, mv_args, pc_args)
    return sol, (sol.x, sol.health, mv_args, pc_args)


def _cg_implicit_bwd(mv_c, pc_c, statics, res, ct):
    x_star, health, mv_args, pc_args = res
    tol, maxiter, stall_window = statics
    # Only x carries a cotangent; diagnostics are non-differentiable.
    xbar = ct.x
    # SolveHealth quarantine: zero the cotangents of faulted columns (their
    # primal iterate is not a solution of A x = b, so the implicit-function
    # identity does not hold there) and scrub non-finite cotangents — a
    # guarded solve never emits NaN gradients.
    keep = (~health.any_fault).astype(x_star.dtype)
    xbar = jnp.where(jnp.isfinite(xbar), xbar, 0.0) * keep
    mv = lambda v: mv_c(v, *mv_args)
    pc = None if pc_c is None else (lambda v: pc_c(v, *pc_args))
    wsol = _cg_plain(mv, xbar, tol=tol, maxiter=maxiter, preconditioner=pc,
                     stall_window=stall_window)
    w = jnp.where(jnp.isfinite(wsol.x), wsol.x, 0.0) * keep
    # b̄ = w;  θ̄ = −vjp_θ(θ ↦ A(θ) x*)(w)  for the hoisted closure args.
    _, pull_args = jax.vjp(lambda a: mv_c(x_star, *a), mv_args)
    (mv_args_bar,) = pull_args(w)
    mv_args_bar = jax.tree_util.tree_map(lambda t: -t, mv_args_bar)
    # The preconditioner changes the iteration, not the solution: zeros.
    pc_args_bar = jax.tree_util.tree_map(jnp.zeros_like, pc_args)
    return w, None, mv_args_bar, pc_args_bar


_cg_implicit.defvjp(_cg_implicit_fwd, _cg_implicit_bwd)


class CGLoopState(NamedTuple):
    """The complete CG loop state — a checkpointable pytree of arrays.

    Snapshotting this mid-solve and resuming reproduces the exact
    trajectory of an uninterrupted run: the loop body is a deterministic
    function of this state alone (the matvec is re-supplied by the caller
    on restart).  ``b``/``tol_abs``/``rhs_bad`` ride along so the exit path
    needs nothing beyond the state and the matvec.
    """

    x: Array
    r: Array
    z: Array
    p: Array
    rz: Array
    iters: Array
    best: Array       # best residual so far (stagnation reference)
    stall: Array      # consecutive non-improving iterations
    poisoned: Array   # SolveHealth.nonfinite accumulator
    stalled: Array    # SolveHealth.stagnated accumulator
    bad: Array        # SolveHealth.breakdown_iter accumulator
    i: Array          # global iteration counter (scalar int32)
    b: Array          # validated right-hand side
    tol_abs: Array
    rhs_bad: Array


class KrylovMachine(NamedTuple):
    """A Krylov solve in resumable form: ``state0`` + pure ``cond``/``body``
    step functions + ``finish``.

    ``while cond(s): s = body(s)`` followed by ``finish(s)`` IS the solver
    (:func:`cg` / :func:`minres` run exactly this); a driver may instead run
    the loop in bounded segments, checkpoint the state pytree between them
    (see :mod:`repro.runtime.durable`), and still produce a bit-identical
    trajectory.
    """

    state: NamedTuple
    cond: Callable
    body: Callable
    finish: Callable


def cg_machine(matvec: Matvec, b: Array, *, x0: Array | None = None,
               tol: float = 1e-8, maxiter: int = 1000,
               preconditioner: Matvec | None = None,
               stall_window: int = 250) -> KrylovMachine:
    """CG as a resumable machine (state pytree: :class:`CGLoopState`)."""
    matvec, b, x0, preconditioner, batched = _as_columns(
        matvec, b, x0, preconditioner)
    rhs_bad, b, x0 = _validate_rhs(b, x0)
    if x0 is None:
        # r0 = b - A·0 = b: skipping the matvec drops one of three copies
        # of the operator graph from the trace (faster compile, same math)
        x, r = jnp.zeros_like(b), b
    else:
        x, r = x0, b - matvec(x0)
    z = preconditioner(r) if preconditioner is not None else r
    p = z
    rz = _col_dot(r, z)  # (C,)
    resn0 = _col_norms(r)
    tol_abs = tol * jnp.maximum(_col_norms(b), 1.0)  # (C,)
    cshape = tol_abs.shape
    state0 = CGLoopState(
        x=x, r=r, z=z, p=p, rz=rz,
        iters=jnp.zeros(cshape, jnp.int32),
        best=resn0,  # best residual so far
        stall=jnp.zeros(cshape, jnp.int32),
        poisoned=jnp.zeros(cshape, bool),
        stalled=jnp.zeros(cshape, bool),
        bad=jnp.full(cshape, -1, jnp.int32),
        i=jnp.zeros((), jnp.int32),
        b=b, tol_abs=tol_abs, rhs_bad=rhs_bad)

    def cond(s: CGLoopState):
        alive = (_col_norms(s.r) > s.tol_abs) & ~s.poisoned & ~s.stalled
        return jnp.logical_and(s.i < maxiter, jnp.any(alive))

    def body(s: CGLoopState):
        x, r, z, p, rz = s.x, s.r, s.z, s.p, s.rz
        best, stall, poisoned, stalled, bad = (
            s.best, s.stall, s.poisoned, s.stalled, s.bad)
        active = (_col_norms(r) > s.tol_abs) & ~poisoned & ~stalled  # (C,)
        ap = matvec(p)
        denom = _col_dot(p, ap)
        alpha = rz / jnp.where(denom != 0, denom, 1.0)
        alpha = jnp.where(active, alpha, 0.0)
        x_new = x + alpha * p
        r_new = r - alpha * ap
        z_new = preconditioner(r_new) if preconditioner is not None else r_new
        rz_new = _col_dot(r_new, z_new)
        beta = jnp.where(active, rz_new / jnp.where(rz != 0, rz, 1.0), 0.0)
        p_new = z_new + beta * p

        # quarantine: a column whose update went non-finite reverts to its
        # last finite iterate and leaves the active set for good — frozen
        # columns never take (or emit) NaN values, so lockstep siblings
        # are untouched
        ok = (jnp.all(jnp.isfinite(x_new), axis=0)
              & jnp.all(jnp.isfinite(r_new), axis=0)
              & jnp.all(jnp.isfinite(p_new), axis=0))
        upd = active & ok
        trip = active & ~ok
        poisoned = poisoned | trip
        bad = jnp.where(trip & (bad < 0), s.i, bad)
        sel = lambda new, old: jnp.where(upd[None, :], new, old)
        x, r, z, p = (sel(x_new, x), sel(r_new, r), sel(z_new, z),
                      sel(p_new, p))
        rz = jnp.where(upd, rz_new, rz)

        # stagnation: no relative improvement over the best residual for
        # stall_window consecutive iterations -> freeze the column
        resn = _col_norms(r)
        improved = resn < best * (1.0 - _STALL_RTOL)
        best = jnp.minimum(best, resn)
        stall = jnp.where(upd & ~improved, stall + 1, 0)
        if stall_window:
            stalled = stalled | (stall >= stall_window)
        return CGLoopState(
            x=x, r=r, z=z, p=p, rz=rz, iters=s.iters + active,
            best=best, stall=stall, poisoned=poisoned, stalled=stalled,
            bad=bad, i=s.i + 1, b=s.b, tol_abs=s.tol_abs,
            rhs_bad=s.rhs_bad)

    def finish(s: CGLoopState) -> SolveResult:
        return _finish(matvec, s.b, s.x, s.tol_abs, s.iters, s.rhs_bad,
                       s.poisoned, s.stalled, s.bad, batched)

    return KrylovMachine(state=state0, cond=cond, body=body, finish=finish)


def _cg_plain(matvec: Matvec, b: Array, *, x0: Array | None = None,
              tol: float = 1e-8, maxiter: int = 1000,
              preconditioner: Matvec | None = None,
              stall_window: int = 250) -> SolveResult:
    """The forward-only CG recurrence (also the implicit VJP's inner solve),
    with its exit true-residual pass, in the ``krylov`` scope."""
    with jax.named_scope(scopes.KRYLOV):
        m = cg_machine(matvec, b, x0=x0, tol=tol, maxiter=maxiter,
                       preconditioner=preconditioner,
                       stall_window=stall_window)
        return m.finish(jax.lax.while_loop(m.cond, m.body, m.state))


class MinresLoopState(NamedTuple):
    """The complete MINRES loop state (see :class:`CGLoopState`)."""

    x: Array
    v: Array
    v_prev: Array
    w: Array
    w_prev: Array
    phi_bar: Array
    delta1: Array
    eps_k: Array
    cs: Array
    sn: Array
    beta: Array
    iters: Array
    best: Array
    stall: Array
    poisoned: Array
    stalled: Array
    bad: Array
    i: Array
    b: Array
    tol_abs: Array
    rhs_bad: Array


def minres_machine(matvec: Matvec, b: Array, *, x0: Array | None = None,
                   tol: float = 1e-8, maxiter: int = 1000,
                   stall_window: int = 250) -> KrylovMachine:
    """MINRES as a resumable machine (state: :class:`MinresLoopState`)."""
    matvec, b, x0, _, batched = _as_columns(matvec, b, x0, None)
    rhs_bad, b, x0 = _validate_rhs(b, x0)
    if x0 is None:
        x, r = jnp.zeros_like(b), b  # r0 = b - A·0 (matvec elided)
    else:
        x, r = x0, b - matvec(x0)
    beta1 = _col_norms(r)  # (C,)
    tol_abs = tol * jnp.maximum(_col_norms(b), 1.0)
    dtype = b.dtype
    eps = jnp.finfo(dtype).tiny
    cshape = beta1.shape

    # Lanczos + Givens QR recurrences (standard MINRES state machine),
    # one independent recurrence per column
    v = r / jnp.maximum(beta1, eps)
    v_prev = jnp.zeros_like(b)
    w = jnp.zeros_like(b)
    w_prev = jnp.zeros_like(b)
    phi_bar = beta1
    delta1 = jnp.zeros(cshape, dtype)
    eps_k = jnp.zeros(cshape, dtype)
    cs = -jnp.ones(cshape, dtype)
    sn = jnp.zeros(cshape, dtype)
    beta = beta1
    state0 = MinresLoopState(
        x=x, v=v, v_prev=v_prev, w=w, w_prev=w_prev, phi_bar=phi_bar,
        delta1=delta1, eps_k=eps_k, cs=cs, sn=sn, beta=beta,
        iters=jnp.zeros(cshape, jnp.int32),
        best=beta1,  # best |phi_bar| so far
        stall=jnp.zeros(cshape, jnp.int32),
        poisoned=jnp.zeros(cshape, bool),
        stalled=jnp.zeros(cshape, bool),
        bad=jnp.full(cshape, -1, jnp.int32),
        i=jnp.zeros((), jnp.int32),
        b=b, tol_abs=tol_abs, rhs_bad=rhs_bad)

    def cond(s: MinresLoopState):
        alive = (jnp.abs(s.phi_bar) > s.tol_abs) & ~s.poisoned & ~s.stalled
        return jnp.logical_and(s.i < maxiter, jnp.any(alive))

    def body(s: MinresLoopState):
        (x, v, v_prev, w, w_prev, phi_bar, delta1, eps_k, cs, sn, beta) = (
            s.x, s.v, s.v_prev, s.w, s.w_prev, s.phi_bar, s.delta1,
            s.eps_k, s.cs, s.sn, s.beta)
        best, stall, poisoned, stalled, bad = (
            s.best, s.stall, s.poisoned, s.stalled, s.bad)
        i = s.i
        active = (jnp.abs(phi_bar) > s.tol_abs) & ~poisoned & ~stalled
        av = matvec(v)
        alpha = _col_dot(v, av).astype(dtype)
        av = av - alpha * v - beta * v_prev
        beta_new = _col_norms(av)
        v_new = av / jnp.maximum(beta_new, eps)

        # previous rotation
        delta2 = cs * delta1 + sn * alpha
        gamma1 = sn * delta1 - cs * alpha
        eps_next = sn * beta_new
        delta1_next = -cs * beta_new

        # new rotation
        gamma2 = jnp.sqrt(gamma1 * gamma1 + beta_new * beta_new)
        gamma2 = jnp.maximum(gamma2, eps)
        cs_new = gamma1 / gamma2
        sn_new = beta_new / gamma2
        tau = cs_new * phi_bar
        phi_bar_new = sn_new * phi_bar

        w_new = (v - delta2 * w - eps_k * w_prev) / gamma2
        x_new = x + tau * w_new

        # per-column freeze: only columns that are active AND whose update
        # stayed finite take the step — everything else (converged,
        # poisoned, stagnated, or tripping this iteration) keeps its whole
        # recurrence state, so NaNs never enter the carried arrays
        ok = (jnp.all(jnp.isfinite(x_new), axis=0)
              & jnp.all(jnp.isfinite(v_new), axis=0)
              & jnp.isfinite(phi_bar_new))
        upd = active & ok
        trip = active & ~ok
        poisoned = poisoned | trip
        bad = jnp.where(trip & (bad < 0), i, bad)
        seln = lambda new, old: jnp.where(upd[None, :], new, old)
        selc = lambda new, old: jnp.where(upd, new, old)
        x2, v2, vp2 = seln(x_new, x), seln(v_new, v), seln(v, v_prev)
        w2, wp2 = seln(w_new, w), seln(w, w_prev)
        phi_bar = selc(phi_bar_new, phi_bar)
        delta1, eps_k = selc(delta1_next, delta1), selc(eps_next, eps_k)
        cs, sn = selc(cs_new, cs), selc(sn_new, sn)
        beta = selc(beta_new, beta)

        # stagnation on the QR-recurrence residual |phi_bar|
        resn = jnp.abs(phi_bar)
        improved = resn < best * (1.0 - _STALL_RTOL)
        best = jnp.minimum(best, resn)
        stall = jnp.where(upd & ~improved, stall + 1, 0)
        if stall_window:
            stalled = stalled | (stall >= stall_window)
        return MinresLoopState(
            x=x2, v=v2, v_prev=vp2, w=w2, w_prev=wp2, phi_bar=phi_bar,
            delta1=delta1, eps_k=eps_k, cs=cs, sn=sn, beta=beta,
            iters=s.iters + active, best=best, stall=stall,
            poisoned=poisoned, stalled=stalled, bad=bad, i=i + 1,
            b=s.b, tol_abs=s.tol_abs, rhs_bad=s.rhs_bad)

    def finish(s: MinresLoopState) -> SolveResult:
        return _finish(matvec, s.b, s.x, s.tol_abs, s.iters, s.rhs_bad,
                       s.poisoned, s.stalled, s.bad, batched)

    return KrylovMachine(state=state0, cond=cond, body=body, finish=finish)


def minres(matvec: Matvec, b: Array, *, x0: Array | None = None,
           tol: float = 1e-8, maxiter: int = 1000,
           stall_window: int = 250) -> SolveResult:
    """MINRES for symmetric (possibly indefinite) operators.

    Batched ``b`` (n, C) runs per-column Lanczos + Givens recurrences in
    lockstep (all scalar recurrence state becomes (C,)-shaped); a frozen
    column — converged, poisoned, or stagnated — stops updating its whole
    recurrence (iterate *and* Lanczos state), so a non-finite column can
    never leak into its siblings.  Guard flags land in ``result.health``;
    ``stall_window=0`` disables stagnation detection.
    """
    m = minres_machine(matvec, b, x0=x0, tol=tol, maxiter=maxiter,
                       stall_window=stall_window)
    return m.finish(jax.lax.while_loop(m.cond, m.body, m.state))


# ---------------------------------------------------------------------------
# Lockstep bank solvers: one bank matvec per iteration for S·C systems.
# ---------------------------------------------------------------------------

def _bank_solve(solver, bank_matvec: Matvec, b: Array, x0: Array | None,
                kwargs) -> SolveResult:
    """Flatten the bank axis into the column axis and run a lockstep solve.

    ``bank_matvec`` maps (S, n, C) -> (S, n, C) applying operator ``s`` to
    ``x[s]`` (e.g. the lockstep flavor of ``FastsumOperatorBank.matvec``);
    the per-column machinery of :func:`cg`/:func:`minres` then gives every
    (s, c) system its own step sizes, tolerance ``tol * max(||b[s,:,c]||,
    1)``, and convergence mask — while each iteration costs exactly one bank
    matvec (one spread + one forward FFT for the whole sweep).
    """
    if b.ndim not in (2, 3):
        raise ValueError(f"bank rhs must be (S, n) or (S, n, C), got {b.shape}")
    squeeze = b.ndim == 2
    b3 = b[..., None] if squeeze else b
    s, n, c = b3.shape

    def flat_mv(u):  # (n, S*C) -> (n, S*C)
        xb = jnp.moveaxis(u.reshape(n, s, c), 1, 0)
        yb = bank_matvec(xb)
        return jnp.moveaxis(yb, 0, 1).reshape(n, s * c)

    def to_flat(v):  # (S, n, C) -> (n, S*C)
        return jnp.moveaxis(v, 0, 1).reshape(n, s * c)

    def from_flat(v):  # (n, S*C) -> (S, n, C)
        return jnp.moveaxis(v.reshape(n, s, c), 1, 0)

    x0f = None if x0 is None else to_flat(x0[..., None] if squeeze else x0)
    sol = solver(flat_mv, to_flat(b3), x0=x0f, **kwargs)
    x = from_flat(sol.x)
    stats = [a.reshape(s, c) for a in
             (sol.num_iters, sol.residual_norm, sol.converged)]
    health = SolveHealth(*(a.reshape(s, c) for a in sol.health))
    if squeeze:
        x = x[..., 0]
        stats = [a[:, 0] for a in stats]
        health = SolveHealth(*(a[:, 0] for a in health))
    return SolveResult(x, *stats, health=health)


def cg_bank(bank_matvec: Matvec, b: Array, *, x0: Array | None = None,
            tol: float = 1e-8, maxiter: int = 1000,
            stall_window: int = 250) -> SolveResult:
    """Lockstep CG over a bank axis: b (S, n) or (S, n, C).

    One bank matvec per iteration solves all S·C systems; per-system
    tolerance masks freeze converged systems; the true residual is
    recomputed at exit.  Result fields mirror the input layout: ``x``
    (S, n[, C]), ``num_iters``/``residual_norm``/``converged`` (S[, C]),
    and ``health`` fields likewise (S[, C]) — a poisoned tenant's system
    is quarantined without touching its bank siblings.
    """
    return _bank_solve(cg, bank_matvec, b, x0,
                       dict(tol=tol, maxiter=maxiter,
                            stall_window=stall_window))


def minres_bank(bank_matvec: Matvec, b: Array, *, x0: Array | None = None,
                tol: float = 1e-8, maxiter: int = 1000,
                stall_window: int = 250) -> SolveResult:
    """Lockstep MINRES over a bank axis (see :func:`cg_bank`)."""
    return _bank_solve(minres, bank_matvec, b, x0,
                       dict(tol=tol, maxiter=maxiter,
                            stall_window=stall_window))
