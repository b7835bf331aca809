"""Spectral clustering (paper Section 6.2.1; Ng–Jordan–Weiss [28]).

Pipeline: k largest eigenvectors of A = D^{-1/2} W D^{-1/2} (computed by the
NFFT-based Lanczos method, the hybrid Nyström, or a direct solver) ->
row-normalize -> k-means on the embedded rows.

k-means (kmeans++ init + Lloyd iterations) is implemented in JAX so the whole
pipeline is one jittable program.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.fastsum import NormalizedAdjacencyOperator
from repro.core.lanczos import eigsh

Array = jax.Array


class KMeansResult(NamedTuple):
    assignments: Array  # (n,)
    centers: Array  # (k, d)
    inertia: Array


def _kmeanspp_init(key: Array, points: Array, k: int) -> Array:
    n = points.shape[0]
    keys = jax.random.split(key, k)
    first = jax.random.randint(keys[0], (), 0, n)
    centers = jnp.zeros((k, points.shape[1]), points.dtype).at[0].set(points[first])

    def body(i, centers):
        d2 = jnp.min(
            jnp.sum((points[:, None, :] - centers[None, :, :]) ** 2, -1)
            + jnp.where(jnp.arange(k)[None, :] < i, 0.0, jnp.inf), axis=1)
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
        idx = jax.random.choice(keys[i], n, p=probs)
        return centers.at[i].set(points[idx])

    return jax.lax.fori_loop(1, k, body, centers)


@functools.partial(jax.jit, static_argnames=("k", "num_iters"))
def kmeans(key: Array, points: Array, k: int, num_iters: int = 50) -> KMeansResult:
    centers = _kmeanspp_init(key, points, k)

    def step(_, centers):
        d2 = jnp.sum((points[:, None, :] - centers[None, :, :]) ** 2, -1)
        assign = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(assign, k, dtype=points.dtype)
        counts = jnp.maximum(one_hot.sum(0), 1.0)
        new_centers = jnp.matmul(one_hot.T, points,
                                 precision=jax.lax.Precision.HIGHEST
                                 ) / counts[:, None]
        # keep empty clusters where they were
        new_centers = jnp.where((one_hot.sum(0) > 0)[:, None], new_centers, centers)
        return new_centers

    centers = jax.lax.fori_loop(0, num_iters, step, centers)
    d2 = jnp.sum((points[:, None, :] - centers[None, :, :]) ** 2, -1)
    assign = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1))
    return KMeansResult(assignments=assign, centers=centers, inertia=inertia)


class SpectralResult(NamedTuple):
    assignments: Array
    eigenvalues: Array
    eigenvectors: Array
    residual_bounds: Array | None = None  # Lanczos bounds; None if given


def spectral_clustering(adjacency: NormalizedAdjacencyOperator, k: int,
                        *, key: Array, num_lanczos_iters: int | None = None,
                        block_size: int = 1,
                        eigenvectors: Array | None = None,
                        eigenvalues: Array | None = None) -> SpectralResult:
    """NJW spectral clustering with NFFT-accelerated eigenvectors.

    Pass precomputed ``eigenvectors`` to reuse (e.g. from Nyström) — then the
    adjacency operator is only used for its size.  ``block_size > 1`` uses
    block Lanczos: the fused fastsum engine applies the operator to whole
    (n, block) batches, amortizing spread/gather across the block.
    """
    # independent streams for the Lanczos start vector and the k-means++
    # init — reusing one key would correlate the two randomizations
    key_eigs, key_kmeans = jax.random.split(key)
    bounds = None
    if eigenvectors is None:
        res = eigsh(adjacency.matvec, adjacency.n, k,
                    num_iters=num_lanczos_iters, key=key_eigs,
                    block_size=block_size,
                    dtype=adjacency.inv_sqrt_deg.dtype)
        eigenvectors, eigenvalues = res.eigenvectors, res.eigenvalues
        bounds = res.residual_bounds
    rows = eigenvectors / jnp.maximum(
        jnp.linalg.norm(eigenvectors, axis=1, keepdims=True), 1e-30)
    km = kmeans(key_kmeans, rows, k)
    return SpectralResult(assignments=km.assignments,
                          eigenvalues=eigenvalues, eigenvectors=eigenvectors,
                          residual_bounds=bounds)


def clustering_agreement(a: Array, b: Array, k: int) -> float:
    """Fraction of points whose cluster assignment agrees between two
    labelings, maximized over label permutations (greedy Hungarian-lite,
    exact for k <= 6 via brute force)."""
    import itertools

    import numpy as np

    a = np.asarray(a)
    b = np.asarray(b)
    best = 0.0
    if k <= 6:
        for perm in itertools.permutations(range(k)):
            mapped = np.asarray(perm)[b]
            best = max(best, float(np.mean(a == mapped)))
        return best
    # greedy fallback
    remaining = set(range(k))
    mapping = {}
    for c in range(k):
        counts = [(np.sum((b == c) & (a == t)), t) for t in remaining]
        cnt, t = max(counts)
        mapping[c] = t
        remaining.discard(t)
    mapped = np.asarray([mapping[x] for x in b])
    return float(np.mean(a == mapped))
