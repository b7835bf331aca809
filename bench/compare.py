"""The numbers that decide ``correct``, each computed against the reference.

Every function takes what the timed path produced and what a
:class:`bench.reference.DirectOperator` built from the same inputs gives
(its degrees, or its product with the returned vectors), and returns plain
floats; a number that is not finite reads ``inf``, so it fails
any limit.
"""

from __future__ import annotations

import itertools
import math

import numpy as np


def _finite(value: float) -> float:
    value = float(value)
    return value if math.isfinite(value) else math.inf


def rel_err(a, b) -> float:
    """``|a - b| / |b|`` in float64."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return _finite(np.linalg.norm(a - b) / np.linalg.norm(b))


def degree_rel_err(degrees, ref) -> float:
    """The build's degrees ``W 1`` against the reference's."""
    return rel_err(degrees, ref.degrees)


def eig_residual_excess(eigenvalues, eigenvectors, bounds, av) -> float:
    """Largest excess of ``|A v - lam v| / |v|`` over the solver's own
    residual bound for the same pair; ``av`` is ``A v`` with ``A`` the
    reference.

    The bound says how far the Krylov subspace has converged; the excess is
    what the operator and the arithmetic add.  A pair without a finite bound
    reads ``inf``.
    """
    lam = np.asarray(eigenvalues, np.float64)
    bounds = np.asarray(bounds, np.float64)
    if not np.all(np.isfinite(bounds)):
        return math.inf
    v64 = np.asarray(eigenvectors, np.float32).astype(np.float64)
    true = (np.linalg.norm(np.asarray(av, np.float64) - v64 * lam[None, :],
                           axis=0)
            / np.linalg.norm(v64, axis=0))
    return _finite(np.max(true - bounds))


def eigval_gap(eigenvalues, eigenvectors, av) -> float:
    """Largest ``|lam - v^T A v / v^T v|``, ``av`` being ``A v`` with ``A``
    the reference: how far each returned eigenvalue lies from the
    reference's Rayleigh quotient of its own returned vector."""
    lam = np.asarray(eigenvalues, np.float64)
    v64 = np.asarray(eigenvectors, np.float32).astype(np.float64)
    quotient = (np.sum(v64 * np.asarray(av, np.float64), axis=0)
                / np.sum(v64 * v64, axis=0))
    return _finite(np.max(np.abs(lam - quotient)))


def label_mismatch(assignments, labels, k: int) -> float:
    """Share of points whose cluster differs from the generator's class,
    under the one-to-one map of clusters to classes that agrees best.

    Classes and clusters are ``0..k-1``; an assignment outside that range
    never agrees.
    """
    a = np.asarray(assignments).astype(np.int64).ravel()
    b = np.asarray(labels).astype(np.int64).ravel()
    ok = (a >= 0) & (a < k) & (b >= 0) & (b < k)
    agree = np.zeros((k, k), np.int64)
    np.add.at(agree, (a[ok], b[ok]), 1)
    best = max(sum(agree[i, perm[i]] for i in range(k))
               for perm in itertools.permutations(range(k)))
    return _finite(1.0 - best / a.size)


def orthogonality(eigenvectors) -> float:
    """``max |V^T V - I|`` of the returned eigenvectors, in float64."""
    v = np.asarray(eigenvectors, np.float64)
    return _finite(np.max(np.abs(v.T @ v - np.eye(v.shape[1]))))


def verdict(numbers: dict, limits: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` for every limit of the cell; a
    limit whose number was not produced reads ``inf``."""
    return {name: {"value": numbers.get(name, math.inf), "limit": limit}
            for name, limit in limits.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
