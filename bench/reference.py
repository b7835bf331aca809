"""The plain reference: the Gaussian graph operator by the direct product.

``W x`` with ``W_ij = exp(-|p_i - p_j|^2 / sigma^2)`` off the diagonal and 0
on it, summed over all n points in blocks of rows: O(n^2) work, O(tile * n)
memory, no dense matrix and no NFFT.  It imports nothing of the program and
takes nothing the program made; the normalized adjacency
``A = D^{-1/2} W D^{-1/2}`` is built from its own degrees ``D = W 1``.

``precision="float32"`` is the reference.  ``precision="bfloat16"`` is the
control: the same product with the points, the kernel values and the vector
rounded to bfloat16 and the result rounded to bfloat16 (sums in float32, as
the chip's matrix unit does), the one step below the float32 that every
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16")


@functools.partial(jax.jit, static_argnames=("sigma", "tile", "precision"))
def kernel_product(points, x, *, sigma: float, tile: int = 256,
                   precision: str = "float32"):
    """``W x`` for points (n, d) and x (n, C); returns (n, C) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    n = points.shape[0]
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    pts = points.astype(dt)
    xs = x.astype(dt)
    pad = (-n) % tile
    rows_all = jnp.pad(pts, ((0, pad), (0, 0)))
    inv_s2 = jnp.asarray(1.0 / (sigma * sigma), dt)
    cols = jnp.arange(n)
    pts_t = pts.T  # (d, n): one row of coordinates per dimension

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(rows_all, i * tile, tile, axis=0)
        # squared distances summed dimension by dimension, so that the
        # whole block fuses into one pass with no (tile, n) intermediate
        r2 = jnp.zeros((tile, n), dt)
        for c in range(pts.shape[1]):
            diff = rows[:, c, None] - pts_t[c][None, :]
            r2 = r2 + diff * diff
        w = jnp.exp(-r2 * inv_s2)  # (tile, n)
        w = jnp.where((i * tile + jnp.arange(tile))[:, None] == cols[None, :],
                      jnp.zeros((), dt), w)
        if precision == "float32":
            return jnp.sum(w[:, :, None] * xs[None, :, :], axis=1)
        return jnp.dot(w, xs, preferred_element_type=jnp.float32).astype(dt)

    out = jax.lax.map(block, jnp.arange((n + pad) // tile))
    return out.reshape(n + pad, -1)[:n].astype(jnp.float32)


class DirectOperator:
    """``W`` and ``A = D^{-1/2} W D^{-1/2}`` of a point set, applied directly.

    ``degrees`` is computed once, at construction.
    """

    def __init__(self, points, sigma: float, *, precision: str = "float32",
                 tile: int = 1024):
        self.points = jnp.asarray(points, jnp.float32)
        self.sigma, self.precision, self.tile = sigma, precision, tile
        self.degrees = self.w(jnp.ones((self.n,), jnp.float32))
        self.inv_sqrt_deg = 1.0 / jnp.sqrt(self.degrees)

    @property
    def n(self) -> int:
        return self.points.shape[0]

    def w(self, x):
        cols = x if x.ndim == 2 else x[:, None]
        out = kernel_product(self.points, cols, sigma=self.sigma,
                             tile=self.tile, precision=self.precision)
        return out if x.ndim == 2 else out[:, 0]

    def a(self, v):
        s = self.inv_sqrt_deg if v.ndim == 1 else self.inv_sqrt_deg[:, None]
        return s * self.w(s * v)


def lanczos_eigsh(matvec, n: int, k: int, steps: int, key, dtype):
    """Largest eigenpairs by plain Lanczos with full reorthogonalisation.

    Vectors and the basis are held in ``dtype``; inner products sum in
    float32.  Returns ``(eigenvalues (k,), eigenvectors (n, k), bounds
    (k,))`` with ``bounds`` the Lanczos residual bounds.
    """
    f32 = jnp.float32

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=f32,
                       precision=jax.lax.Precision.HIGHEST)

    q = jax.random.normal(key, (n,), f32)
    q = (q / jnp.linalg.norm(q)).astype(dtype)
    basis, alphas, betas = [], [], []
    for _ in range(steps):
        basis.append(q)
        w = matvec(q.astype(f32)).astype(dtype)
        alphas.append(dot(q, w))
        qs = jnp.stack(basis)
        for _ in range(2):  # classical Gram-Schmidt, twice
            w = (w.astype(f32) - dot(dot(qs, w).astype(dtype), qs)
                 ).astype(dtype)
        beta = jnp.linalg.norm(w.astype(f32))
        betas.append(beta)
        q = (w.astype(f32) / beta).astype(dtype)
    t = (jnp.diag(jnp.stack(alphas)) + jnp.diag(jnp.stack(betas[:-1]), 1)
         + jnp.diag(jnp.stack(betas[:-1]), -1))
    theta, s = jnp.linalg.eigh(t)
    top = jnp.argsort(-theta)[:k]
    vecs = dot(jnp.stack(basis).T, s[:, top].astype(dtype))
    bounds = jnp.abs(betas[-1] * s[-1, top])
    return theta[top], vecs, bounds


def spectral_labels(eigenvectors, k: int, seed: int, iters: int = 50):
    """Clusters of the rows of ``eigenvectors`` (n, k), each row scaled to
    unit length: k-means++ seeding, then ``iters`` Lloyd steps, in numpy
    float64.  Returns ``(n,)`` int labels."""
    rng = np.random.default_rng(seed)
    v = np.asarray(eigenvectors, np.float64)
    rows = v / np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-30)
    centers = [rows[rng.integers(rows.shape[0])]]
    d2 = np.sum((rows - centers[0]) ** 2, axis=1)
    for _ in range(1, k):
        centers.append(rows[rng.choice(rows.shape[0], p=d2 / d2.sum())])
        d2 = np.minimum(d2, np.sum((rows - centers[-1]) ** 2, axis=1))
    centers = np.stack(centers)
    for _ in range(iters):
        dist = (np.sum(rows ** 2, 1)[:, None] - 2 * rows @ centers.T
                + np.sum(centers ** 2, 1)[None, :])
        labels = np.argmin(dist, axis=1)
        for c in range(k):
            if np.any(labels == c):
                centers[c] = rows[labels == c].mean(axis=0)
    dist = (np.sum(rows ** 2, 1)[:, None] - 2 * rows @ centers.T
            + np.sum(centers ** 2, 1)[None, :])
    return np.argmin(dist, axis=1)

