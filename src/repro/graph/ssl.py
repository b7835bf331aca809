"""Semi-supervised learning on graphs (paper Sections 6.2.2 and 6.2.3).

1. Phase-field / Allen–Cahn method (Bertozzi–Flenner [5]):
   convexity-split semi-implicit time stepping of

       u_t = -eps L_s u - (1/eps) psi'(u) + Omega (f - u)

   projected on the k smallest eigenpairs of L_s.  Binary labels +-1; the
   multiclass driver runs one-vs-rest.

2. Kernel method (Zhou et al. [48]):  solve  (I + beta L_s) u = f  by CG with
   NFFT matvecs (Eq. (6.4)), or with a truncated eigenapproximation
   V_k diag(1-lam_k) V_k^T of A for O(nk) solves.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import node_order, scopes
from repro.core.fastsum import (
    FastsumParams, NormalizedAdjacencyOperator,
    make_normalized_adjacency_mixture,
)
from repro.core.lanczos import eigsh
from repro.core.solvers import cg

Array = jax.Array


def make_training_vector(labels: Array, n_samples_per_class: int, n_classes: int,
                         *, key: Array, positive_class: int) -> tuple[Array, Array]:
    """Binary training vector f (+1 for positive class samples, -1 for other
    class samples, 0 elsewhere) and the sample mask (paper Section 6.2.2).

    Per-class sample counts are clamped to the class size, so classes with
    fewer than ``n_samples_per_class`` members contribute all their members
    and nothing else (the selection must never spill past the class into the
    sentinel rows and label wrong-class nodes).  Eager-only: the clamp reads
    concrete class sizes from ``labels``.
    """
    n = labels.shape[0]
    f = jnp.zeros((n,))
    mask = jnp.zeros((n,), bool)
    keys = jax.random.split(key, n_classes)
    for c in range(n_classes):
        members = labels == c
        take = min(n_samples_per_class, int(jnp.sum(members)))
        if take == 0:
            continue
        idx = jnp.where(members, jax.random.uniform(keys[c], (n,)), 2.0)
        chosen = jnp.argsort(idx)[:take]
        sign = jnp.where(c == positive_class, 1.0, -1.0)
        f = f.at[chosen].set(sign)
        mask = mask.at[chosen].set(True)
    return f, mask


class PhaseFieldResult(NamedTuple):
    u: Array
    num_steps: int


def allen_cahn_ssl(eigenvalues_ls: Array, eigenvectors: Array, f: Array,
                   *, eps: float = 10.0, tau: float = 0.1,
                   omega0: float = 10_000.0, c: float | None = None,
                   max_steps: int = 500, rtol: float = 1e-10) -> PhaseFieldResult:
    """Allen–Cahn SSL in the truncated eigenbasis (Section 6.2.2).

    ``eigenvalues_ls``: k smallest eigenvalues of L_s; ``eigenvectors``:
    corresponding (n, k) eigenvectors; ``f``: training vector (+-1 / 0).
    """
    if c is None:
        c = 2.0 / eps + omega0
    v = eigenvectors  # (n, k)
    lam = eigenvalues_ls  # (k,)
    omega = (f != 0).astype(f.dtype) * omega0

    denom = 1.0 + tau * (eps * lam + c)  # (k,)

    u0 = f
    a0 = v.T @ u0

    def step(carry):
        a_bar, u_bar, i, _ = carry
        psi_prime = 4.0 * u_bar * (u_bar * u_bar - 1.0)
        # Discrete convexity-split form (paper Section 6.2.2):
        # (1 + tau(eps lam + c)) a = a_bar + tau(-(1/eps) v^T psi'(u_bar)
        #                                        + c a_bar + v^T Omega (f-u_bar))
        rhs = (a_bar
               + tau * (-(1.0 / eps) * (v.T @ psi_prime)
                        + c * a_bar
                        + v.T @ (omega * (f - u_bar))))
        a_new = rhs / denom
        u_new = v @ a_new
        rel = jnp.sum((u_new - u_bar) ** 2) / jnp.maximum(jnp.sum(u_bar ** 2), 1e-30)
        return a_new, u_new, i + 1, rel

    def cond(carry):
        _, _, i, rel = carry
        return jnp.logical_and(i < max_steps, rel > rtol)

    a, u, steps, _ = jax.lax.while_loop(
        cond, step, (a0, u0, jnp.zeros((), jnp.int32), jnp.ones(())))
    return PhaseFieldResult(u=u, num_steps=int(steps))


def allen_cahn_multiclass(adjacency: NormalizedAdjacencyOperator, labels: Array,
                          n_classes: int, n_samples_per_class: int, *,
                          k: int = 5, key: Array,
                          num_lanczos_iters: int | None = None,
                          eigsh_fn: Callable | None = None,
                          **ac_kwargs) -> Array:
    """One-vs-rest Allen–Cahn classification.  Returns predicted labels."""
    res = (eigsh_fn or (lambda: eigsh(
        adjacency.matvec, adjacency.n, k, num_iters=num_lanczos_iters,
        key=key, dtype=adjacency.inv_sqrt_deg.dtype)))()
    lam_ls = 1.0 - res.eigenvalues  # smallest of L_s
    scores = []
    for cls in range(n_classes):
        f, _ = make_training_vector(labels, n_samples_per_class, n_classes,
                                    key=jax.random.fold_in(key, cls),
                                    positive_class=cls)
        out = allen_cahn_ssl(lam_ls, res.eigenvectors, f, **ac_kwargs)
        scores.append(out.u)
    return jnp.argmax(jnp.stack(scores, axis=1), axis=1)


def training_matrix(labels: Array, n_classes: int) -> Array:
    """The right-hand side of kernel SSL from partial labels (Section 6.2.2).

    ``labels`` (n,) holds a node's class in ``0..n_classes-1`` where it is
    labelled and ``-1`` where it is not.  Two classes give the binary
    vector f (n,): +1 at the labelled nodes of class 1, -1 at those of class
    0.  More classes give the one-vs-rest matrix F (n, n_classes): column c
    holds +1 at the labelled nodes of class c and -1 at the other labelled
    nodes.  Unlabelled nodes are 0 everywhere.  Traceable under ``jit``.
    """
    labelled = labels >= 0
    if n_classes == 2:
        return jnp.where(labelled, jnp.where(labels == 1, 1.0, -1.0), 0.0)
    own = labels[:, None] == jnp.arange(n_classes)
    return jnp.where(labelled[:, None], jnp.where(own, 1.0, -1.0), 0.0)


def predicted_labels(u: Array) -> Array:
    """Classes of a kernel SSL solution: for a binary u (n,), 1 where
    u > 0 and 0 elsewhere; for one-vs-rest columns (n, C), the column of
    the largest value."""
    if u.ndim == 1:
        return (u > 0).astype(jnp.int32)
    return jnp.argmax(u, axis=1).astype(jnp.int32)


class KernelSSLResult(NamedTuple):
    u: Array
    num_iters: Array
    converged: Array


def kernel_ssl_cg(adjacency: NormalizedAdjacencyOperator, f: Array, beta: float,
                  *, tol: float = 1e-4, maxiter: int = 1000) -> KernelSSLResult:
    """Solve (I + beta L_s) u = f with CG + NFFT matvecs (Eq. (6.4)).

    ``f`` (n,) is one binary right-hand side; ``f`` (n, C) holds C of them,
    e.g. the one-vs-rest columns of :func:`training_matrix`, solved as C
    CG recurrences in lockstep with one operator application on all C
    columns per iteration (:func:`repro.core.solvers.cg`).  ``u`` has
    ``f``'s shape; ``num_iters`` and ``converged`` are scalars for ``f``
    (n,) and (C,) per column for ``f`` (n, C).  The solve runs
    ``max(num_iters)`` applications, then one more for the exit true
    residual.

    Where the adjacency has a node order (:mod:`repro.core.node_order`),
    the system is composed from its application in that order, so CG runs
    there: ``f`` is permuted in once and ``u`` out once.
    """
    order = node_order.of(adjacency.matvec)
    adjacency_matvec = adjacency.matvec if order is None else order.matvec

    def matvec(x):
        return x + beta * (x - adjacency_matvec(x))

    if order is None:
        sol = cg(matvec, f, tol=tol, maxiter=maxiter)
        u = sol.x
    else:
        with jax.named_scope(scopes.KRYLOV):
            (f_rows,) = node_order.rows(order, f)
        sol = cg(matvec, f_rows, tol=tol, maxiter=maxiter)
        with jax.named_scope(scopes.KRYLOV):
            u = node_order.nodes(order, sol.x)
    return KernelSSLResult(u=u, num_iters=sol.num_iters,
                           converged=sol.converged)


def kernel_ssl_cg_multilayer(kernels, weights, points: Array,
                             params: FastsumParams, f: Array, beta: float,
                             *, tol: float = 1e-4, maxiter: int = 1000
                             ) -> KernelSSLResult:
    """Kernel SSL on an aggregated multilayer graph (one matvec per layer sum).

    The multilayer extension (Bergermann–Stoll–Volkmer 2020) builds the
    weight matrix as a fixed-weight sum of per-layer kernels,
    ``W = sum_l w_l (W̃_l - K_l(0) I)``, over shared nodes.  Because the
    per-layer operators share their NFFT plan and window geometry, the
    mixture collapses to a *single* summed spectral multiplier
    (:func:`repro.core.fastsum.make_normalized_adjacency_mixture`): every CG
    iteration on (I + beta L_s) costs exactly one fused matvec, the same as
    a single-layer graph — not |layers| of them.
    """
    adjacency = make_normalized_adjacency_mixture(kernels, weights, points,
                                                  params)
    return kernel_ssl_cg(adjacency, f, beta, tol=tol, maxiter=maxiter)


def kernel_ssl_eig(eigenvalues_a: Array, eigenvectors: Array, f: Array,
                   beta: float) -> Array:
    """Same solve via truncated eigenapproximation of A (Section 6.2.3).

    With A ≈ V diag(theta) V^T:  L_s ≈ I - V diag(theta) V^T, and by
    Sherman–Morrison–Woodbury
        (I + beta L_s)^{-1} = ((1+beta) I - beta V diag(theta) V^T)^{-1}
      = (1/(1+beta)) [ I + V diag( beta theta / (1+beta-beta theta) ) V^T ].
    """
    theta = eigenvalues_a
    coeff = beta * theta / (1.0 + beta - beta * theta)
    vtf = eigenvectors.T @ f
    return (f + eigenvectors @ (coeff * vtf)) / (1.0 + beta)
