"""d-dimensional NFFT (nonequispaced fast Fourier transform) in pure JAX.

Conventions (matching the paper, Section 3):

    forward :  f_j    = sum_{l in I_N^d} f_hat[l] * e^{+2 pi i l . v_j}
    adjoint :  x_hat[l] = sum_j x_j * e^{-2 pi i l . v_j}

with ``I_N = {-N/2, ..., N/2-1}`` and nodes ``v_j in [-1/2, 1/2)^d``.
Coefficient arrays have shape ``(N,)*d`` in FFT order (no fftshift anywhere).

Algorithm (Keiner–Kunis–Potts): oversampled grid of size ``M = sigma_os * N``
per dimension, compactly supported window ``phi`` with cut-off ``m``
(support ``|x| <= m/M``), Kaiser–Bessel by default.

    forward:  deconvolve (divide by phi_hat) -> embed I_N into I_M ->
              unnormalized inverse FFT scaled by 1/M^d (= jnp.fft.ifftn) ->
              gather with window taps at each node.
    adjoint:  exact matrix adjoint of the forward: spread (scatter-add) ->
              fftn -> extract I_N -> deconvolve (divide by M^d * phi_hat).

Because the two transforms are *exact* matrix adjoints of one another, the
fast-summation operator  F . diag(b_hat) . F^H  is exactly Hermitian for real
``b_hat`` — the Lanczos method below operates on a genuinely symmetric
operator, not an approximately-symmetric one.

TPU adaptation (DESIGN.md §3): node sets are static across Krylov iterations,
so window geometry is precomputed once and reused by every matvec.  The hot
path uses the *separable* :class:`WindowGeometry` (O(n*d*taps) values)
consumed by the streaming window backends in ``repro.core.fastsum_exec`` /
``repro.kernels.nfft_window``; the flattened tensor-product
:class:`NfftGeometry` (O(n*taps^d) values) survives only for the two-NFFT
oracle transforms below.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

KAISER_BESSEL = "kaiser_bessel"
GAUSSIAN_WINDOW = "gaussian"


@dataclasses.dataclass(frozen=True)
class NfftPlan:
    """Static NFFT parameters (hashable; used as a jit static argument)."""

    d: int
    n_bandwidth: int  # N, even
    m: int  # window cut-off
    sigma_os: float = 2.0  # oversampling factor
    window: str = KAISER_BESSEL

    def __post_init__(self):
        assert self.n_bandwidth % 2 == 0, "bandwidth N must be even"
        assert self.d >= 1 and self.m >= 1

    @property
    def grid_size(self) -> int:
        """Oversampled grid size M per dimension (even, >= sigma_os*N)."""
        m_grid = int(np.ceil(self.sigma_os * self.n_bandwidth / 2) * 2)
        return max(m_grid, self.n_bandwidth + 2 * self.m + 2)

    @property
    def taps(self) -> int:
        return 2 * self.m + 1

    # -- window ------------------------------------------------------------
    def window_b(self) -> float:
        sigma = self.grid_size / self.n_bandwidth
        if self.window == KAISER_BESSEL:
            return float(np.pi * (2.0 - 1.0 / sigma))
        if self.window == GAUSSIAN_WINDOW:
            return float((2.0 * sigma / (2.0 * sigma - 1.0)) * self.m / np.pi)
        raise ValueError(self.window)

    def window_spatial(self, x: Array) -> Array:
        """phi(x), normalized by e^{-b m} (KB) to stay finite in f32.

        The normalization cancels inside each transform because ``phi`` is
        always paired with a division by ``phi_hat`` carrying the same factor.
        """
        m, grid = self.m, self.grid_size
        b = self.window_b()
        if self.window == KAISER_BESSEL:
            t = m * m - (grid * x) ** 2
            s = jnp.sqrt(jnp.maximum(t, 0.0))
            # sinh(b s)/(pi s) * e^{-b m}, computed overflow-free:
            #   = e^{b(s-m)} (1 - e^{-2 b s}) / (2 pi s)
            num = jnp.exp(b * (s - m)) * (1.0 - jnp.exp(-2.0 * b * s))
            safe_s = jnp.where(s > 1e-12, s, 1.0)
            val = jnp.where(s > 1e-12, num / (2.0 * jnp.pi * safe_s), b * jnp.exp(-b * m) / jnp.pi)
            return jnp.where(t >= 0, val, 0.0)
        if self.window == GAUSSIAN_WINDOW:
            val = jnp.exp(-((grid * x) ** 2) / b) / jnp.sqrt(jnp.pi * b)
            return jnp.where(jnp.abs(grid * x) <= m, val, 0.0)
        raise ValueError(self.window)

    def window_fourier_1d(self, k: Array) -> Array:
        """phi_hat(k) per dimension, same e^{-b m} normalization as spatial."""
        m, grid = self.m, self.grid_size
        b = self.window_b()
        if self.window == KAISER_BESSEL:
            arg = b * b - (2.0 * jnp.pi * k / grid) ** 2
            s = jnp.sqrt(jnp.maximum(arg, 0.0))
            # I_0(m s) e^{-b m} = i0e(m s) e^{m s - b m};  m s <= b m.
            val = jax.scipy.special.i0e(m * s) * jnp.exp(m * s - b * m)
            # |k| beyond the valid band never occurs for |k| <= N/2 < M/2 when
            # sigma_os >= 1.5; clamp defensively.
            return jnp.where(arg >= 0, val, jnp.exp(-b * m)) / grid
        if self.window == GAUSSIAN_WINDOW:
            return jnp.exp(-b * (jnp.pi * k / grid) ** 2) / grid
        raise ValueError(self.window)

    def deconvolution_grid(self) -> np.ndarray:
        """prod_t phi_hat(l_t) on the (N,)*d coefficient grid, FFT order.

        Cached per plan (the plan is frozen/hashable) as a numpy constant —
        callers no longer rebuild the grid per transform, and jit traces
        embed it as a literal instead of re-staging the window evaluation.
        """
        return _deconvolution_grid_cached(self)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NfftGeometry:
    """Flattened tensor-product window geometry (oracle transforms only).

    The fused engine and both streaming window backends run on the separable
    :class:`WindowGeometry`; this O(n*taps^d) layout is kept for the
    two-NFFT reference path (`nfft_forward`/`nfft_adjoint`) and the dry-run
    cells.

    indices: (n, taps^d) int32 — flattened oversampled-grid indices.
    weights: (n, taps^d) float — tensor-product window values.
    perm: optional (n,) int32 — when present, row ``r`` holds the geometry of
      node ``perm[r]`` (rows are sorted in Morton/tile order so the window
      gather/spread kernels get spatial locality).  ``None`` means rows are in
      node order.
    """

    indices: Array
    weights: Array
    perm: Array | None = None

    def tree_flatten(self):
        return (self.indices, self.weights, self.perm), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_nodes(self) -> int:
        return self.indices.shape[0]


def morton_codes(cells: Array, grid_size: int, dtype=jnp.int32) -> Array:
    """Z-order (Morton) codes for integer cell coordinates (n, d).

    Interleaves the bits of the per-dimension cell indices; sorting by the
    code orders nodes in tiles so neighbouring rows touch neighbouring grid
    memory.  The caller must pick a ``dtype`` wide enough for
    ``bits(grid_size) * d`` interleaved bits (int32 covers every paper
    setup: e.g. grid 128, d=3 -> 21 bits).
    """
    n, d = cells.shape
    bits = max(1, int(grid_size - 1).bit_length())
    assert bits * d <= jnp.iinfo(dtype).bits - 2, (bits, d, dtype)
    code = jnp.zeros((n,), dtype=dtype)
    cells = cells.astype(dtype)
    for b in range(bits):
        for t in range(d):
            code = code | (((cells[:, t] >> b) & 1) << (b * d + t))
    return code


def _morton_keys(cells: Array, grid_size: int) -> Array | None:
    """Morton codes of the cells, or None for grids too large to encode.

    Plans whose interleaved code would overflow int32 use int64 when x64 is
    enabled; otherwise sorting is skipped (identity order) — ordering is a
    layout optimization, never a semantic requirement.
    """
    bits = max(1, int(grid_size - 1).bit_length()) * cells.shape[1]
    if bits <= 30:
        return morton_codes(cells, grid_size)
    if jax.config.jax_enable_x64 and bits <= 62:
        return morton_codes(cells, grid_size, dtype=jnp.int64)
    return None


def _morton_perm(cells: Array, grid_size: int) -> Array:
    """argsort by Morton code (identity order for huge grids)."""
    codes = _morton_keys(cells, grid_size)
    if codes is None:
        return jnp.arange(cells.shape[0], dtype=jnp.int32)
    return jnp.argsort(codes).astype(jnp.int32)


def _window_taps_1d(plan: NfftPlan, nodes: Array):
    """Per-dim tap indices (unwrapped) and window values for nodes (n, d).

    Returns (base, idx_d, w_d): base (n, d) int32 leftmost tap per dim,
    idx_d (n, d, taps) unwrapped grid indices, w_d (n, d, taps) weights.
    """
    grid, m, taps = plan.grid_size, plan.m, plan.taps
    y = nodes * grid  # grid-scaled positions, per dim
    base = jnp.floor(y).astype(jnp.int32) - m  # (n, d)
    offs = jnp.arange(taps, dtype=jnp.int32)  # (taps,)
    idx_d = base[:, :, None] + offs[None, None, :]  # (n, d, taps)
    dist = nodes[:, :, None] - idx_d.astype(nodes.dtype) / grid
    w_d = plan.window_spatial(dist)  # (n, d, taps)
    return base, idx_d, w_d


@functools.partial(jax.jit, static_argnames=("plan", "sort"))
def build_geometry(plan: NfftPlan, nodes: Array, *,
                   sort: bool = True) -> NfftGeometry:
    """Window geometry for nodes (n, d) in [-1/2, 1/2)^d.

    With ``sort=True`` (default) rows are ordered by the Morton code of the
    node's base grid cell and the permutation is recorded in ``perm``; the
    transforms below undo it, so results are independent of ``sort``.
    """
    n, d = nodes.shape
    assert d == plan.d, (d, plan.d)
    grid = plan.grid_size

    base, idx_d, w_d = _window_taps_1d(plan, nodes)
    idx_mod = jnp.mod(idx_d, grid)  # periodic wrap

    # tensor product across dims -> (n, taps^d)
    flat_idx = idx_mod[:, 0, :]
    flat_w = w_d[:, 0, :]
    for t in range(1, d):
        flat_idx = flat_idx[:, :, None] * grid + idx_mod[:, t, None, :]
        flat_w = flat_w[:, :, None] * w_d[:, t, None, :]
        flat_idx = flat_idx.reshape(n, -1)
        flat_w = flat_w.reshape(n, -1)
    perm = None
    if sort:
        cells = jnp.mod(base + plan.m, grid)  # node cell, in [0, grid)
        perm = _morton_perm(cells, grid)
        flat_idx = flat_idx[perm]
        flat_w = flat_w[perm]
    return NfftGeometry(indices=flat_idx, weights=flat_w, perm=perm)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WindowGeometry:
    """Separable window geometry for the fused fastsum engine.

    Stores O(n*d*taps) data instead of the O(n*taps^d) tensor-product arrays
    of :class:`NfftGeometry` — the fused spread/gather recompute the tensor
    product on the fly and address the padded grid with whole (taps,)^d
    windows (one `lax.scatter_add`/`lax.gather` window per node).

    base: (n, d) int32 — leftmost tap corner, shifted into [0, grid_size)
      (the padded-grid coordinate system; see ``pad_width``).
    weights: (n, d, taps) — per-dimension window values.
    perm: (n,) int32 — rows are Morton-sorted; row ``r`` is node ``perm[r]``.
      ``None`` when the rows already are in node order (the per-shard
      geometry of the distributed matvec, whose caller pre-permutes, and
      :meth:`in_row_order`).
    inv: (n,) int32 — the inverse permutation: node ``i`` is row
      ``inv[i]``.  Built once with ``perm``; ``None`` where ``perm`` is.
    """

    base: Array
    weights: Array
    perm: Array
    inv: Array = None

    def tree_flatten(self):
        return (self.base, self.weights, self.perm, self.inv), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_nodes(self) -> int:
        return self.base.shape[0]

    def in_row_order(self) -> "WindowGeometry":
        """The same rows addressed in their own (Morton) order: a spread
        takes node values already in row order, and a gather returns them
        so, with no permutation either way."""
        return WindowGeometry(base=self.base, weights=self.weights,
                              perm=None)


def window_shift(plan: NfftPlan) -> int:
    """Offset from unwrapped tap coordinates to padded-grid coordinates."""
    return plan.grid_size // 2 + plan.m


def padded_grid_size(plan: NfftPlan) -> int:
    """Per-dim size of the wrap-padded grid the fused engine scatters into."""
    return plan.grid_size + plan.taps - 1


def _morton_sort(cells: Array, grid_size: int, nodes: Array):
    """Nodes in Morton order of their cells: ``(perm, nodes[perm])``.

    One stable multi-operand sort carries the node coordinates along with
    the codes, so no row gather follows it: on a mesh, a gather by a
    sharded permutation partitions into a masked local gather plus an
    all-reduce, where the sort only all-gathers its (rows, d + 2) operands.
    Same order as :func:`_morton_perm`.
    """
    iota = jnp.arange(cells.shape[0], dtype=jnp.int32)
    codes = _morton_keys(cells, grid_size)
    if codes is None:
        return iota, nodes
    out = jax.lax.sort((codes, iota) + tuple(nodes.T), num_keys=1,
                       is_stable=True)
    return out[1], jnp.stack(out[2:], axis=1)


@functools.partial(jax.jit, static_argnames=("plan", "sort"))
def build_window_geometry(plan: NfftPlan, nodes: Array, *,
                          sort: bool = True) -> WindowGeometry:
    """Separable (fused-engine) window geometry for nodes in [-1/2, 1/2)^d."""
    n, d = nodes.shape
    assert d == plan.d, (d, plan.d)
    iota = jnp.arange(n, dtype=jnp.int32)
    perm = inv = iota
    if sort:
        cells = (jnp.floor(nodes * plan.grid_size).astype(jnp.int32)
                 - plan.m + window_shift(plan))  # the rows' base corners
        perm, nodes = _morton_sort(cells, plan.grid_size, nodes)
        # the one index scatter of the permutation, here and not in every
        # gather that restores node order
        inv = jnp.zeros_like(perm).at[perm].set(iota)
    base, _, w_d = _window_taps_1d(plan, nodes)
    return WindowGeometry(base=base + window_shift(plan), weights=w_d,
                          perm=perm, inv=inv)


def _window_fourier_1d_np(plan: NfftPlan, k: np.ndarray) -> np.ndarray:
    """Numpy twin of :meth:`NfftPlan.window_fourier_1d`.

    The cached grids below must be plain numpy: a jnp computation would be
    staged into whichever jit trace first touches the cache, and the cached
    tracer would leak into every later trace.
    """
    import scipy.special

    m, grid = plan.m, plan.grid_size
    b = plan.window_b()
    if plan.window == KAISER_BESSEL:
        arg = b * b - (2.0 * np.pi * k / grid) ** 2
        s = np.sqrt(np.maximum(arg, 0.0))
        val = scipy.special.i0e(m * s) * np.exp(m * s - b * m)
        return np.where(arg >= 0, val, np.exp(-b * m)) / grid
    if plan.window == GAUSSIAN_WINDOW:
        return np.exp(-b * (np.pi * k / grid) ** 2) / grid
    raise ValueError(plan.window)


@functools.lru_cache(maxsize=None)
def _deconvolution_grid_cached(plan: NfftPlan) -> np.ndarray:
    freqs = np.fft.fftfreq(plan.n_bandwidth, d=1.0 / plan.n_bandwidth)
    one_d = _window_fourier_1d_np(plan, freqs)
    out = one_d
    for _ in range(plan.d - 1):
        out = out[..., None] * one_d
    return out


@functools.lru_cache(maxsize=None)
def _embed_map(plan: NfftPlan) -> np.ndarray:
    """Per-dim index map from FFT-order I_N positions to I_M positions."""
    n, grid = plan.n_bandwidth, plan.grid_size
    k = np.fft.fftfreq(n, d=1.0 / n).astype(np.int32)  # signed freqs
    return np.mod(k, grid)


@functools.partial(jax.jit, static_argnames=("plan",))
def nfft_forward(plan: NfftPlan, geometry: NfftGeometry, f_hat: Array) -> Array:
    """Forward NFFT.  f_hat: (N,)*d [+ trailing batch dim C] -> (n,) [ ,C]."""
    d, n_bw, grid = plan.d, plan.n_bandwidth, plan.grid_size
    batched = f_hat.ndim == d + 1
    if not batched:
        f_hat = f_hat[..., None]
    c = f_hat.shape[-1]

    phi_hat = plan.deconvolution_grid()
    g_hat = f_hat / phi_hat[..., None]

    emb = _embed_map(plan)
    # place the (N,)*d block into the (M,)*d grid via advanced indexing
    mesh = jnp.meshgrid(*([emb] * d), indexing="ij")
    big = jnp.zeros((grid,) * d + (c,), dtype=g_hat.dtype)
    big = big.at[tuple(mesh)].set(g_hat)

    g = jnp.fft.ifftn(big, axes=tuple(range(d)))  # (M,)*d + (C,)
    g_flat = g.reshape(-1, c)

    vals = g_flat[geometry.indices.reshape(-1)].reshape(*geometry.indices.shape, c)
    out = jnp.sum(vals * geometry.weights[..., None].astype(vals.dtype), axis=1)
    if geometry.perm is not None:  # rows are Morton-sorted: restore node order
        out = jnp.zeros_like(out).at[geometry.perm].set(out)
    return out if batched else out[..., 0]


@functools.partial(jax.jit, static_argnames=("plan",))
def nfft_adjoint(plan: NfftPlan, geometry: NfftGeometry, x: Array) -> Array:
    """Adjoint NFFT.  x: (n,) [+ trailing batch dim C] -> (N,)*d [ ,C]."""
    d, n_bw, grid = plan.d, plan.n_bandwidth, plan.grid_size
    batched = x.ndim == 2
    if not batched:
        x = x[..., None]
    c = x.shape[-1]

    if geometry.perm is not None:  # rows are Morton-sorted: align x with rows
        x = x[geometry.perm]
    vals = geometry.weights[..., None].astype(jnp.result_type(x, geometry.weights)) * x[:, None, :]
    g_flat = jnp.zeros((grid ** d, c), dtype=vals.dtype)
    g_flat = g_flat.at[geometry.indices.reshape(-1)].add(vals.reshape(-1, c))

    g_hat = jnp.fft.fftn(g_flat.reshape((grid,) * d + (c,)), axes=tuple(range(d)))

    emb = _embed_map(plan)
    mesh = jnp.meshgrid(*([emb] * d), indexing="ij")
    small = g_hat[tuple(mesh)]

    phi_hat = plan.deconvolution_grid()
    out = small / ((grid ** d) * phi_hat)[..., None]
    return out if batched else out[..., 0]


# ---------------------------------------------------------------------------
# Reference (oracle) implementations — O(n N^d), used only in tests.
# ---------------------------------------------------------------------------

def ndft_forward(n_bandwidth: int, nodes: Array, f_hat: Array) -> Array:
    d = nodes.shape[1]
    freqs = jnp.fft.fftfreq(n_bandwidth, d=1.0 / n_bandwidth)
    grids = jnp.meshgrid(*([freqs] * d), indexing="ij")
    l = jnp.stack([g.reshape(-1) for g in grids], axis=-1)  # (N^d, d)
    phase = jnp.exp(2j * jnp.pi * (nodes @ l.T))  # (n, N^d)
    flat = f_hat.reshape(n_bandwidth ** d, *f_hat.shape[d:])
    return phase @ flat.astype(phase.dtype)


def ndft_adjoint(n_bandwidth: int, nodes: Array, x: Array) -> Array:
    d = nodes.shape[1]
    freqs = jnp.fft.fftfreq(n_bandwidth, d=1.0 / n_bandwidth)
    grids = jnp.meshgrid(*([freqs] * d), indexing="ij")
    l = jnp.stack([g.reshape(-1) for g in grids], axis=-1)
    phase = jnp.exp(-2j * jnp.pi * (l @ nodes.T))  # (N^d, n)
    out = phase @ x.astype(phase.dtype)
    return out.reshape((n_bandwidth,) * d + x.shape[1:])
