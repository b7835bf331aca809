"""The plain reference of kernel SSL and the numbers compared against it.

Kernel SSL (paper Sec. 6.2.2, Eq. (6.4)) solves ``(I + beta L_s) u = f``
with ``L_s = I - A`` and ``A = D^{-1/2} W D^{-1/2}``.  Here ``A`` is
:class:`bench.reference.DirectOperator`'s direct product, and the solver is
plain CG from zero, every column of ``f`` a recurrence of its own, with
inner products summed in float32 at ``Precision.HIGHEST``.  It imports
nothing of the program: the right-hand side, the labels and the numbers are
all made here from the instance.

With the float32 operator and vectors this is the reference.  With the
bfloat16 operator and vectors, run for as many iterations as the program
took, it is the control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import DirectOperator


def rhs(given: np.ndarray, n_classes: int) -> np.ndarray:
    """``f`` (n, 1) or (n, n_classes) float32 from partial labels (``-1``
    where unlabelled): for two classes one column, +1 at the labelled nodes
    of class 1 and -1 at those of class 0; else one-vs-rest columns, +1 at
    the labelled nodes of the column's class, -1 at the other labelled
    nodes."""
    labelled = given >= 0
    if n_classes == 2:
        own = (given == 1)[:, None]
    else:
        own = given[:, None] == np.arange(n_classes)[None, :]
    return np.where(labelled[:, None], np.where(own, 1.0, -1.0),
                    0.0).astype(np.float32)


def labels(u) -> np.ndarray:
    """Classes of a solution in :func:`rhs`'s layout: ``u > 0`` for one
    column, the column of the largest value for several."""
    u = np.asarray(u, np.float32)
    u = u if u.ndim == 2 else u[:, None]
    if u.shape[1] == 1:
        return (u[:, 0] > 0).astype(np.int32)
    return np.argmax(u, axis=1).astype(np.int32)


def system(op: DirectOperator, beta: float):
    """``u -> (I + beta L_s) u`` with ``op``'s ``A``."""
    return lambda u: u + beta * (u - op.a(u))


def _col_dot(a, b):
    return jnp.einsum("nc,nc->c", a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def cg(apply, f, *, tol: float | None = None, steps: int = 1000,
       dtype=jnp.float32):
    """Plain CG from zero for ``apply(u) = f``, ``f`` (n, C).

    Every column is a recurrence of its own, and one that reaches
    ``|r| <= tol * max(|f_c|, 1)`` takes no further step; the solve stops
    when every column has, or after ``steps`` iterations (all of them with
    ``tol=None``).  Vectors are held in ``dtype``.  Returns ``(u (n, C)
    float32, iterations)``.
    """
    f32 = jnp.float32
    f = jnp.asarray(f, f32)
    x = jnp.zeros_like(f).astype(dtype)
    r = f.astype(dtype)
    p = r
    rr = _col_dot(r, r)
    stop = (None if tol is None
            else tol * jnp.maximum(jnp.sqrt(_col_dot(f, f)), 1.0))
    it = 0
    while it < steps:
        active = (jnp.ones_like(rr, bool) if stop is None
                  else jnp.sqrt(rr) > stop)
        if not bool(jnp.any(active)):
            break
        ap = apply(p.astype(f32)).astype(dtype)
        pap = _col_dot(p, ap)
        alpha = jnp.where(active, rr / jnp.where(pap != 0, pap, 1.0), 0.0)
        x = (x.astype(f32) + alpha * p.astype(f32)).astype(dtype)
        r = (r.astype(f32) - alpha * ap.astype(f32)).astype(dtype)
        rr_new = _col_dot(r, r)
        step = jnp.where(active, rr_new / jnp.where(rr != 0, rr, 1.0), 0.0)
        p = jnp.where(active, r.astype(f32) + step * p.astype(f32),
                      p.astype(f32)).astype(dtype)
        rr = jnp.where(active, rr_new, rr)
        it += 1
    return x.astype(f32), it


def _columns(v) -> np.ndarray:
    v = np.asarray(v, np.float32)
    return v if v.ndim == 2 else v[:, None]


def true_residual(f: np.ndarray, u, beta: float, ref: DirectOperator) -> float:
    """Largest over the columns of ``|f - (u + beta (u - A u))| / |f|``,
    ``A`` the reference's, in float64; ``inf`` where not finite."""
    u = _columns(u)
    au = np.asarray(ref.a(jnp.asarray(u)), np.float64)
    u64 = u.astype(np.float64)
    res = f.astype(np.float64) - (u64 + beta * (u64 - au))
    value = float(np.max(np.linalg.norm(res, axis=0)
                         / np.linalg.norm(f.astype(np.float64), axis=0)))
    return value if math.isfinite(value) else math.inf


def label_mismatch(assigned, reference, unlabelled: np.ndarray) -> float:
    """Share of the unlabelled nodes whose class differs from the
    reference's."""
    a = np.asarray(assigned).astype(np.int64).ravel()
    b = np.asarray(reference).astype(np.int64).ravel()
    return float(np.mean(a[unlabelled] != b[unlabelled]))
