"""Runtime accuracy guards: Lemma 3.1 consulted live (paper Section 3.1).

``core/error.py`` implements the paper's a-posteriori bound

    ||A - A_E||_inf <= eps (1 + eta) / (eta (eta - eps)),   eps < eta,

but nothing in the live stack consulted it — a mis-sized bandwidth produced
silently wrong eigenvalues and predictions.  This module closes that gap
with a *cheap* probe (no O(n^2) dense matrix):

* ``eta = d_min / ||W||_inf`` from one approximate-degree matvec (Eq. 3.5:
  for non-negative W the inf-norm is the max row sum, i.e. the max degree);
* ``eps`` from the Monte-Carlo regularization-error sweep of
  :func:`repro.core.error.estimate_epsilon` (Eq. 3.6) — O(n_samples)
  kernel evaluations against the trigonometric polynomial.

:func:`guarded_fastsum` builds an operator, probes it, and escalates the
bandwidth ``N`` (doubling up to ``GuardPolicy.max_bandwidth``) until the
bound meets the declared tolerance.  If escalation runs out and the problem
is small enough, it degrades to the exact O(n^2)
:class:`DirectKernelOperator` (the bottom rung of the degradation ladder:
pencil -> psum, fastsum -> direct); otherwise it returns the
best attempt with ``GuardReport.ok = False`` and a warning — degraded,
never silently wrong.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp

from repro.core.error import estimate_epsilon, lemma31_bound
from repro.core.fastsum import (
    FastsumOperator, FastsumParams, _normalized_adjacency_from,
    direct_matvec_tiled, make_fastsum, scale_nodes,
)
from repro.core.kernels import Kernel

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GuardPolicy:
    """Knobs for the accuracy guard (see README "Robustness").

    ``bound_tol``
        maximum admissible Lemma 3.1 bound on ``||A - A_E||_inf``.
    ``max_bandwidth``
        escalation ceiling for the fastsum bandwidth ``N``.
    ``direct_threshold``
        problem size at/below which the exact O(n^2) fallback is allowed
        when escalation runs out.
    ``n_probe_samples`` / ``seed``
        Monte-Carlo budget for the eps estimator (deterministic per seed).
    """

    bound_tol: float = 5e-2
    max_bandwidth: int = 256
    direct_threshold: int = 8192
    n_probe_samples: int = 2048
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ProbeReport:
    """One probe of one operator: the Lemma 3.1 ingredients + bound."""

    n_bandwidth: int
    eta: float
    eps: float
    bound: float


@dataclasses.dataclass
class GuardReport:
    """Outcome of a guarded build: every attempt, and what was returned.

    ``fallback`` is ``"none"`` (a fastsum operator was returned) or
    ``"direct"`` (the exact dense-math fallback).  ``ok`` is False only
    when no attempt met the tolerance *and* the direct fallback was not
    admissible — the returned operator is then the best attempt and its
    bound is ``final.bound``.
    """

    attempts: list[ProbeReport]
    fallback: str
    ok: bool

    @property
    def final(self) -> ProbeReport:
        return self.attempts[-1]

    @property
    def escalations(self) -> int:
        return len(self.attempts) - 1


@dataclasses.dataclass
class DirectKernelOperator:
    """Exact O(n^2)-FLOP kernel-sum operator — the degradation-ladder floor.

    Duck-compatible with :class:`~repro.core.fastsum.FastsumOperator`'s
    matvec surface (``matvec`` / ``matvec_tilde`` / ``degrees`` /
    ``n_source``), backed by :func:`~repro.core.fastsum.direct_matvec_tiled`
    (O(n*tile) memory, never materializes W).  Its error is exactly zero:
    below ``GuardPolicy.direct_threshold`` the guard prefers slow-and-exact
    over fast-and-out-of-tolerance.
    """

    kernel: Kernel
    points: Array
    tile: int = 2048

    @property
    def n_source(self) -> int:
        return self.points.shape[0]

    @property
    def n_target(self) -> int:
        return self.n_source

    def matvec(self, x: Array, *, backend: str | None = None) -> Array:
        del backend  # dense path has no window backend
        return direct_matvec_tiled(self.kernel, self.points, x,
                                   tile=self.tile)

    def matvec_tilde(self, x: Array, *, backend: str | None = None) -> Array:
        del backend
        return self.matvec(x) + self.kernel.at_zero() * x

    def degrees(self) -> Array:
        return self.matvec(jnp.ones((self.n_source,), self.points.dtype))


def probe_fastsum(kernel: Kernel, points: Array, params: FastsumParams,
                  fastsum: FastsumOperator | None = None, *,
                  n_samples: int = 2048, seed: int = 0) -> ProbeReport:
    """Cheap a-posteriori probe of one operator (no dense W).

    One approximate-degree matvec gives ``eta`` (Eq. 3.5); the Monte-Carlo
    regularization-error sweep gives ``eps`` (Eq. 3.6).  O(n + n_samples).
    """
    if fastsum is None:
        fastsum = make_fastsum(kernel, points, params)
    deg = fastsum.degrees()
    if not bool(jnp.all(jnp.isfinite(deg))):
        # a poisoned operator cannot even report degrees: worst bound
        return ProbeReport(params.n_bandwidth, 0.0, float("inf"),
                           float("inf"))
    w_inf = max(float(jnp.max(deg)), float(jnp.finfo(deg.dtype).tiny))
    eta = max(float(jnp.min(deg)), 0.0) / w_inf
    _, rho, _ = scale_nodes(jnp.asarray(points), params.eps_b_eff)
    eps = estimate_epsilon(kernel.rescaled(float(rho)), fastsum,
                           points.shape[0], w_inf,
                           n_samples=n_samples, seed=seed)
    return ProbeReport(params.n_bandwidth, eta, eps,
                       lemma31_bound(eta, eps))


def guarded_fastsum(kernel: Kernel, points: Array, params: FastsumParams,
                    *, policy: GuardPolicy = GuardPolicy()):
    """Build a fastsum operator whose Lemma 3.1 bound meets the tolerance.

    Returns ``(operator, GuardReport)``.  Escalates ``N`` (doubling) while
    the bound exceeds ``policy.bound_tol``; degrades to
    :class:`DirectKernelOperator` below ``policy.direct_threshold`` when the
    ceiling is reached; past the threshold returns the best attempt with
    ``report.ok = False`` and a warning.
    """
    points = jnp.asarray(points)
    attempts: list[ProbeReport] = []
    p = params
    while True:
        op = make_fastsum(kernel, points, p)
        rep = probe_fastsum(kernel, points, p, op,
                            n_samples=policy.n_probe_samples,
                            seed=policy.seed)
        attempts.append(rep)
        if rep.bound <= policy.bound_tol:
            return op, GuardReport(attempts, "none", True)
        if 2 * p.n_bandwidth > policy.max_bandwidth:
            break
        p = dataclasses.replace(p, n_bandwidth=2 * p.n_bandwidth)
    if points.shape[0] <= policy.direct_threshold:
        return (DirectKernelOperator(kernel, points),
                GuardReport(attempts, "direct", True))
    warnings.warn(
        f"accuracy guard: Lemma 3.1 bound {attempts[-1].bound:.3g} exceeds "
        f"tol {policy.bound_tol:.3g} at the bandwidth ceiling "
        f"N={attempts[-1].n_bandwidth} and n={points.shape[0]} is above the "
        f"direct-fallback threshold; returning the best attempt UNGUARDED",
        RuntimeWarning, stacklevel=2)
    return op, GuardReport(attempts, "none", False)


def guarded_normalized_adjacency(kernel: Kernel, points: Array,
                                 params: FastsumParams, *,
                                 policy: GuardPolicy = GuardPolicy()):
    """Guarded Algorithm 3.2: normalized adjacency over a guarded operator.

    Returns ``(NormalizedAdjacencyOperator, GuardReport)`` — the adjacency
    is built over whichever operator (escalated fastsum or exact direct)
    the guard settled on; Lanczos/eigsh consumers read the report to know
    the error budget their Ritz values inherit.
    """
    op, report = guarded_fastsum(kernel, points, params, policy=policy)
    return _normalized_adjacency_from(op), report
