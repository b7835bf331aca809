"""Fused fast-summation execution engine (plan once, execute many).

The seed implementation of Algorithm 3.1 ran two independent NFFTs per
matvec: spread -> complex FFT -> extract I_N -> deconvolve, then deconvolve
-> embed I_N -> complex IFFT -> gather, rebuilding the deconvolution grid
and paying an O(n * taps^d) scalar scatter/gather against tensor-product
geometry arrays each call.  This module fuses the whole pipeline into

    spread -> rfftn -> multiply -> irfftn -> gather

around one precomputed spectral multiplier on the full oversampled grid:

    C[k] = b_hat[k] / (M^d * phi_hat[k]^2)   for k in I_N^d (zero-padded
                                              into I_M^d, FFT order)

Hermitian-symmetrized so that the real-to-complex FFT pair computes exactly
the real part the two-NFFT path produced: for real input the adjoint's
spectrum is Hermitian, and

    Re(ifftn(C . fftn(g))) = irfftn(sym(C) . rfftn(g)),
    sym(C)[k] = (C[k] + conj(C[-k])) / 2,

where the only asymmetric bins of C are the I_N Nyquist rows that have no
mirror inside I_N.  No embed/extract scatter, no per-call deconvolution,
and the two full complex FFTs become one real FFT pair (half the flops and
spectrum memory).

The window step uses the separable geometry of :class:`~repro.core.nfft.
WindowGeometry` (per-dim patch corner + per-dim weights, O(n * d * taps)
values; nodes Morton-sorted by ``build_window_geometry`` so consecutive
windows touch neighbouring grid tiles) and runs on one of two streaming
backends selected by ``backend="auto"|"xla"|"pallas"``:

* ``"xla"`` (the CPU/portable fallback and the parity oracle): a
  ``fori_loop`` over Morton-sorted node tiles, each step one
  `lax.scatter_add` / `lax.gather` of the tile's whole (taps,)^d windows.
  Peak memory is O(tile * taps^d * C) with the tile sized to a fixed
  element budget — the (n, taps^d, C) update cube of the PR 2 whole-window
  path is never materialized.

* ``"pallas"`` (`repro.kernels.nfft_window`): Morton-sorted node tiles
  stream through VMEM against the resident padded grid, held lane-dense
  (last spatial axis on the lanes, channels leading); each node
  scatter-adds into / gathers from only the (taps,)^d patch it touches,
  with the weight tensor product kept in-register.  The window step
  converts between the engine's ``(P,)*d + (C,)`` grid and that layout,
  and splits the channels into as few kernel calls as VMEM holds.

``backend="auto"`` (the default everywhere) picks pallas on TPU when one
channel of the resident grid fits VMEM and xla otherwise
(:func:`resolve_backend`), so ``FastsumOperator.matvec``, block Lanczos,
and the distributed matvec pick the kernels up transparently.

Everything is natively multi-RHS: ``x`` of shape (n,) or (n, C) flows
through with a trailing channel dimension on the grid, so block Lanczos /
multi-column solves amortize spread and gather over the batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scopes
from repro.core.nfft import (
    NfftPlan, WindowGeometry, _embed_map, padded_grid_size, window_shift,
)
from repro.kernels import nfft_window, ops as kernel_ops

Array = jax.Array

BACKENDS = ("auto", "xla", "pallas")


def resolve_backend(backend: str | None, plan: NfftPlan, channels: int,
                    dtype) -> str:
    """Resolve the window-step backend for one spread or gather.

    ``"auto"`` picks the Pallas kernels on TPU when their inputs are
    float32 and one channel of the resident padded grid fits VMEM
    (:func:`repro.kernels.nfft_window.grid_fits_vmem`, the one threshold),
    and the XLA path otherwise: off TPU, for float64 (Mosaic has no 64-bit
    floats), and for grids too large to stay resident.  ``channels`` does
    not change the choice: more channels than one call holds run in
    chunks that fit (:func:`_channel_chunks`).  Everything it reads is
    static, so the choice is made once per traced shape, and a kernel
    that then fails to compile raises — nothing falls back behind the
    caller's back.

    An *explicit* ``"pallas"`` off-TPU runs the kernels in interpret mode —
    the per-node streaming loop executed by the Pallas emulator.  That is
    the parity-testing path (bit-identical semantics to the TPU lowering),
    not a performance path; benchmarks must not time it.
    """
    if backend is None or backend == "auto":
        if jax.default_backend() != "tpu" or \
                jnp.dtype(dtype) != jnp.float32:
            return "xla"
        fits = nfft_window.grid_fits_vmem(padded_grid_size(plan), plan.d, 1)
        return "pallas" if fits else "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {backend!r}")
    return backend


def fused_spectral_multiplier(plan: NfftPlan, b_hat: Array) -> Array:
    """Combined multiplier, Hermitian-symmetrized, as an rfftn half-spectrum.

    Returns shape ``(M,)*(d-1) + (M//2 + 1,)`` complex, FFT order.
    """
    d, grid = plan.d, plan.grid_size
    phi_hat = plan.deconvolution_grid()  # (N,)*d real
    small = b_hat / ((grid ** d) * phi_hat * phi_hat)
    emb = _embed_map(plan)
    mesh = jnp.meshgrid(*([emb] * d), indexing="ij")
    big = jnp.zeros((grid,) * d, dtype=small.dtype).at[tuple(mesh)].set(small)
    # conj-reflect: rev[k] = big[(-k) mod M] along every axis
    rev = big
    for ax in range(d):
        rev = jnp.roll(jnp.flip(rev, axis=ax), 1, axis=ax)
    sym = 0.5 * (big + jnp.conj(rev))
    return sym[..., : grid // 2 + 1]


@functools.lru_cache(maxsize=None)
def spectral_support(plan: NfftPlan) -> tuple:
    """Per-dim indices where the fused multiplier is nonzero (half-spectrum).

    The symmetrized zero-padded I_N block occupies ``[0..N/2]`` and
    ``[M-N/2..M-1]`` per leading dimension and ``[0..N/2]`` along the rfft
    axis — about N^d/2 coefficients, the minimal block a distributed matvec
    has to all-reduce (half the seed's N^d complex psum payload).
    """
    n, grid = plan.n_bandwidth, plan.grid_size
    # plain numpy: jnp values built here would be staged into (and leak out
    # of) whichever jit trace first populates the cache
    full = np.concatenate([np.arange(n // 2 + 1),
                           np.arange(grid - n // 2, grid)]).astype(np.int32)
    half = np.arange(n // 2 + 1, dtype=np.int32)
    return tuple([full] * (plan.d - 1) + [half])


# Streamed-tile budget for the XLA window step, in weight-cube elements per
# tile (tile size = _XLA_TILE_ELEMS / taps^d nodes): bounds peak memory at
# ~1 MiB f64 per channel regardless of n, taps, d.
_XLA_TILE_ELEMS = 1 << 17


def _xla_node_tile(n: int, taps: int, d: int) -> int:
    return max(64, min(n, _XLA_TILE_ELEMS // taps ** d))


def _tile_weight_cube(w: Array, d: int) -> Array:
    """Tensor product of per-dim weights: (t, d, taps) -> (t,) + (taps,)*d."""
    t, _, taps = w.shape
    cube = w[:, 0]
    for ax in range(1, d):
        cube = cube[..., None] * w[:, ax].reshape((t,) + (1,) * ax + (taps,))
    return cube


def _xla_spread(plan: NfftPlan, geometry: WindowGeometry, xs: Array) -> Array:
    """Streaming tiled spread: fori_loop over Morton-sorted node tiles.

    ``xs`` is already in row (Morton) order.  Each step scatter-adds the
    whole-(taps,)^d windows of one node tile, so peak memory is
    O(tile * taps^d * C) (~:data:`_XLA_TILE_ELEMS` elements per channel) —
    never the full (n, taps^d, C) update cube.
    """
    d, taps = plan.d, plan.taps
    pad_n = padded_grid_size(plan)
    n, c = xs.shape
    tile = _xla_node_tile(n, taps, d)
    pad = (-n) % tile
    # padded rows carry zero weights: their windows add exact zeros at 0
    base = jnp.pad(geometry.base, ((0, pad), (0, 0)))
    w = jnp.pad(geometry.weights, ((0, pad), (0, 0), (0, 0)))
    xp = jnp.pad(xs, ((0, pad), (0, 0)))
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, d + 2)),
        inserted_window_dims=(),
        scatter_dims_to_operand_dims=tuple(range(d)))

    def body(k, g):
        bt = jax.lax.dynamic_slice_in_dim(base, k * tile, tile, axis=0)
        wt = jax.lax.dynamic_slice_in_dim(w, k * tile, tile, axis=0)
        xt = jax.lax.dynamic_slice_in_dim(xp, k * tile, tile, axis=0)
        cube = _tile_weight_cube(wt, d)  # (tile,) + (taps,)*d
        updates = cube[..., None] * xt[
            (slice(None),) + (None,) * d + (slice(None),)]
        return jax.lax.scatter_add(
            g, bt, updates, dnums,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    gpad = jnp.zeros((pad_n,) * d + (c,), dtype=xs.dtype)
    num_tiles = (n + pad) // tile
    if num_tiles == 1:
        return body(0, gpad)
    return jax.lax.fori_loop(0, num_tiles, body, gpad)


def _xla_gather_windowed(plan: NfftPlan, geometry: WindowGeometry,
                         gpad: Array) -> Array:
    """Streaming tiled whole-window gather (transpose of :func:`_xla_spread`).

    The fast single-channel body: one `lax.gather` of (taps,)^d + (C,)
    window slices per node tile.  XLA CPU expands gathers to per-element
    loops, and this slice shape hits the cheap expansion only for C = 1 —
    multi-channel inputs route through :func:`_xla_gather` instead.
    """
    d, taps = plan.d, plan.taps
    c = gpad.shape[-1]
    n = geometry.base.shape[0]
    tile = _xla_node_tile(n, taps, d)
    pad = (-n) % tile
    base = jnp.pad(geometry.base, ((0, pad), (0, 0)))
    w = jnp.pad(geometry.weights, ((0, pad), (0, 0), (0, 0)))
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, d + 2)),
        collapsed_slice_dims=(),
        start_index_map=tuple(range(d)))

    def body(k, acc):
        bt = jax.lax.dynamic_slice_in_dim(base, k * tile, tile, axis=0)
        wt = jax.lax.dynamic_slice_in_dim(w, k * tile, tile, axis=0)
        vals = jax.lax.gather(
            gpad, bt, dnums, slice_sizes=(taps,) * d + (c,),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        out = jnp.sum(vals * _tile_weight_cube(wt, d)[..., None],
                      axis=tuple(range(1, d + 1)))  # (tile, C)
        return jax.lax.dynamic_update_slice_in_dim(acc, out, k * tile, axis=0)

    acc = jnp.zeros((n + pad, c), dtype=gpad.dtype)
    num_tiles = (n + pad) // tile
    if num_tiles == 1:
        return body(0, acc)[:n]
    return jax.lax.fori_loop(0, num_tiles, body, acc)[:n]


# Multi-channel gather strategy thresholds, tuned empirically on CPU (see
# the PR 5 sweep benchmark): XLA expands every gather into a per-element
# loop, and the windowed (taps,)^d + (C,) slice expansion is ~3-5x slower
# per element for C >= 2 than for C = 1.  A per-channel lax.map of the fast
# C = 1 body restores the good constant (linear in C); for small d and
# enough channels, a flat-index row take is better still — its ~constant
# per-index overhead amortizes over the C contiguous channel values.
_XLA_GATHER_TAKE_MIN_C = 6
_XLA_GATHER_TAKE_MAX_D = 2
_XLA_TAKE_TILE_ELEMS = 1 << 18


def _xla_gather_take(plan: NfftPlan, geometry: WindowGeometry,
                     gpad: Array) -> Array:
    """Flat-index tiled gather: one row take per (node, window element).

    Gathers rows of the channel-flattened grid by precomputed flat indices
    (static per-plan cube offsets + per-node flat corners) and contracts the
    weight cube per tile.  Per-index cost is ~constant in C, so this wins
    for many channels when taps^d is small (d <= 2).
    """
    d, taps = plan.d, plan.taps
    pad_n = padded_grid_size(plan)
    c = gpad.shape[-1]
    n = geometry.base.shape[0]
    gflat = gpad.reshape(-1, c)
    # static flat offsets of the (taps,)^d window cube (numpy: jit-literal)
    offs = np.arange(taps)
    cube = offs
    for _ in range(d - 1):
        cube = cube[..., None] * pad_n + offs
    cube_off = jnp.asarray(cube.reshape(-1), jnp.int32)
    fb = geometry.base[:, 0]
    for t in range(1, d):
        fb = fb * pad_n + geometry.base[:, t]
    tile = max(64, min(n, _XLA_TAKE_TILE_ELEMS // taps ** d))
    pad = (-n) % tile
    fbp = jnp.pad(fb, (0, pad))
    w = jnp.pad(geometry.weights, ((0, pad), (0, 0), (0, 0)))

    def body(k, acc):
        fbt = jax.lax.dynamic_slice_in_dim(fbp, k * tile, tile)
        wt = jax.lax.dynamic_slice_in_dim(w, k * tile, tile, axis=0)
        idx = (fbt[:, None] + cube_off[None, :]).reshape(-1)
        vals = jnp.take(gflat, idx, axis=0,
                        unique_indices=False).reshape(tile, -1, c)
        wcube = _tile_weight_cube(wt, d).reshape(tile, -1)
        out = jnp.einsum("ntc,nt->nc", vals, wcube)
        return jax.lax.dynamic_update_slice_in_dim(acc, out, k * tile, axis=0)

    acc = jnp.zeros((n + pad, c), dtype=gpad.dtype)
    num_tiles = (n + pad) // tile
    if num_tiles == 1:
        return body(0, acc)[:n]
    return jax.lax.fori_loop(0, num_tiles, body, acc)[:n]


def _xla_gather(plan: NfftPlan, geometry: WindowGeometry,
                gpad: Array) -> Array:
    """Streaming tiled gather, row order — multi-channel aware.

    Dispatches between three equivalent bodies on the (static) channel
    count: the whole-window slice gather for C = 1 (XLA's cheap expansion),
    a flat-index row take for many channels at small d, and a per-channel
    ``lax.map`` of the C = 1 body otherwise.  The multi-channel paths keep
    the bank matvec's inverse half from dominating a sweep: the batched
    windowed gather costs ~3-5x more *per element* as soon as C >= 2.
    """
    c = gpad.shape[-1]
    if c == 1:
        return _xla_gather_windowed(plan, geometry, gpad)
    if plan.d <= _XLA_GATHER_TAKE_MAX_D and c >= _XLA_GATHER_TAKE_MIN_C:
        return _xla_gather_take(plan, geometry, gpad)
    gm = jnp.moveaxis(gpad, -1, 0)[..., None]  # (C,) + grid + (1,)
    out = jax.lax.map(
        lambda g1: _xla_gather_windowed(plan, geometry, g1)[..., 0], gm)
    return jnp.moveaxis(out, 0, 1)


def _channel_chunks(plan: NfftPlan, channels: int) -> list:
    """Channel slices of the Pallas calls: as few as hold the grid in
    VMEM (:func:`repro.kernels.nfft_window.channels_per_call`)."""
    width = nfft_window.channels_per_call(padded_grid_size(plan), plan.d,
                                          channels)
    return [slice(k, min(k + width, channels))
            for k in range(0, channels, width)]


def _flat_boundary(x: Array) -> Array:
    """Node values ``(n, C)`` passed through a flat vector that XLA keeps.

    The kernels read and write node values as rows of an ``(n, C)`` array,
    which the TPU lays out with the channels on the 128 lanes: 128 times
    the bytes at one channel.  Left to itself, XLA gives that layout to
    whatever makes or takes the values, a Krylov solve's vectors over its
    whole loop, rather than convert once per call.  A flat vector behind an
    optimization barrier has a dense layout only, so the conversion happens
    here, once per call.
    """
    n, c = x.shape
    return jax.lax.optimization_barrier(x.reshape(n * c)).reshape(n, c)


def _pallas_spread(plan: NfftPlan, geometry: WindowGeometry,
                   xs: Array) -> Array:
    """The Pallas spread, channel chunk by chunk, in the engine's
    ``(P,)*d + (C,)`` layout."""
    pad_n = padded_grid_size(plan)
    xs = _flat_boundary(xs)
    blocks = [kernel_ops.window_spread(xs[:, cs], geometry.base,
                                       geometry.weights, padded_size=pad_n)
              for cs in _channel_chunks(plan, xs.shape[-1])]
    return nfft_window.from_grid_block(jnp.concatenate(blocks, axis=0),
                                       pad_n, plan.d)


def _pallas_gather(plan: NfftPlan, geometry: WindowGeometry,
                   gpad: Array) -> Array:
    """The Pallas gather of a ``(P,)*d + (C,)`` grid, chunk by chunk."""
    block = nfft_window.to_grid_block(gpad, plan.d)
    outs = [kernel_ops.window_gather(block[cs], geometry.base,
                                     geometry.weights)
            for cs in _channel_chunks(plan, gpad.shape[-1])]
    return _flat_boundary(jnp.concatenate(outs, axis=1))


def window_spread(plan: NfftPlan, geometry: WindowGeometry, x: Array, *,
                  backend: str | None = None) -> Array:
    """Spread node values (n, C) onto the oversampled grid -> (M,)*d + (C,).

    Streams separable (taps,)^d windows into a wrap-padded grid on the
    selected backend, then folds the pad back and aligns to FFT order.
    """
    d, grid, taps = plan.d, plan.grid_size, plan.taps
    pad_n = padded_grid_size(plan)
    with jax.named_scope(scopes.SPREAD):
        # align node values with the Morton-sorted rows
        xs = x if geometry.perm is None else x[geometry.perm]
        if resolve_backend(backend, plan, xs.shape[-1], xs.dtype) == "pallas":
            gpad = _pallas_spread(plan, geometry, xs)
        else:
            gpad = _xla_spread(plan, geometry, xs)
        # fold the periodic pad back: unwrapped u and u - M are the same cell
        ext = taps - 1
        for ax in range(d):
            main = jax.lax.slice_in_dim(gpad, 0, grid, axis=ax)
            tail = jax.lax.slice_in_dim(gpad, grid, pad_n, axis=ax)
            idx = (slice(None),) * ax + (slice(0, ext),)
            gpad = main.at[idx].add(tail)
        # padded coordinate u <-> FFT-order index (u - shift) mod M
        return jnp.roll(gpad, (-window_shift(plan),) * d,
                        axis=tuple(range(d)))


def window_gather(plan: NfftPlan, geometry: WindowGeometry, g: Array, *,
                  backend: str | None = None) -> Array:
    """Gather node values from the grid (M,)*d + (C,) -> (n, C).

    Exact transpose of :func:`window_spread` (same geometry, same weights):
    wrap-pad the grid, stream one (taps,)^d window gather per node on the
    selected backend, then restore node order (none to restore where the
    geometry has no ``perm``).
    """
    d, taps = plan.d, plan.taps
    with jax.named_scope(scopes.GATHER):
        rolled = jnp.roll(g, (window_shift(plan),) * d, axis=tuple(range(d)))
        gpad = jnp.pad(rolled, [(0, taps - 1)] * d + [(0, 0)], mode="wrap")
        if resolve_backend(backend, plan, g.shape[-1], g.dtype) == "pallas":
            out = _pallas_gather(plan, geometry, gpad)
        else:
            out = _xla_gather(plan, geometry, gpad)
        if geometry.perm is None:
            return out
        if geometry.inv is None:
            raise ValueError("a geometry with a permutation needs its "
                             "inverse (build_window_geometry makes both)")
        # restore node order with the geometry's inverse permutation as a
        # row *take*: the equivalent multi-channel row scatter costs ~10x
        # more on XLA CPU
        return out[geometry.inv]


# ---------------------------------------------------------------------------
# Differentiable core (custom VJP).
#
# The pipeline is linear in both x and the spectral multiplier, and
# window_spread / window_gather are exact mutual adjoints on a shared
# geometry (same base/weights/perm; verified to 1e-12 by the adjoint test
# suite).  That gives the whole matvec a closed-form transpose that never
# differentiates *through* the fori_loop scatter tiles or the Pallas
# kernels:
#
#     cotangent wrt x:  spread ybar on the TARGET geometry (gather-adjoint),
#                       run the adjoint spectral mid-section, gather on the
#                       SOURCE geometry — one extra pipeline pass;
#     cotangent wrt multiplier_half:  elementwise product of the forward
#                       spectrum rfftn(g) and the cotangent spectrum.  The
#                       rfftn half-spectrum stores each interior Hermitian
#                       bin once but it appears twice in the full spectrum,
#                       so interior bins (last-axis index not in {0, M/2})
#                       carry weight 2 and the product is conjugated per the
#                       complex chain rule.  Rather than hand-rolling those
#                       weights we take jax.vjp over the FFT-only
#                       mid-section (rfftn -> multiply -> irfftn contains no
#                       scatter/gather), which bakes in exactly that
#                       double-count via the native irfftn/rfftn transposes
#                       and is consistent with finite differences by
#                       construction.
#
# Plan-time geometry (points, Morton windows, permutations) is
# intentionally NON-differentiable: its cotangents are zero (None).  The
# distributed/faulted variants (spectral_reduce / spectral_op / grid_hook)
# bypass the custom VJP and stay forward-only.
# ---------------------------------------------------------------------------

def _spectral_mid(plan: NfftPlan, multiplier_half: Array, g: Array) -> Array:
    """rfftn -> multiply -> irfftn on the spread grid (single multiplier)."""
    d = plan.d
    with jax.named_scope(scopes.FFT_MID):
        g_hat = jnp.fft.rfftn(g, axes=tuple(range(d)))
        g_hat = g_hat * multiplier_half.astype(g_hat.dtype)[..., None]
        y = jnp.fft.irfftn(g_hat, s=(plan.grid_size,) * d,
                           axes=tuple(range(d)))
        return y.astype(g.dtype)


def _bank_multiply(plan: NfftPlan, multiplier_bank: Array, g_hat: Array,
                   broadcast: bool) -> Array:
    """Bank spectral multiply -> flat (..., S*C) half-spectrum product."""
    d = plan.d
    nb = multiplier_bank.shape[0]
    mb = jnp.moveaxis(multiplier_bank, 0, -1)  # spectrum + (S,)
    if broadcast:
        gh = g_hat[..., None, :]  # spectrum + (1, C): broadcast over S
    else:
        c = g_hat.shape[-1] // nb
        gh = g_hat.reshape(g_hat.shape[:d] + (nb, c))
    prod = mb[..., :, None].astype(g_hat.dtype) * gh  # spectrum + (S, C)
    return prod.reshape(prod.shape[:d] + (-1,))


def _bank_spectral_mid(plan: NfftPlan, broadcast: bool,
                       multiplier_bank: Array, g: Array) -> Array:
    """Bank rfftn -> member-wise multiply -> irfftn (no reduce/op hooks)."""
    d = plan.d
    with jax.named_scope(scopes.FFT_MID):
        g_hat = jnp.fft.rfftn(g, axes=tuple(range(d)))
        flat = _bank_multiply(plan, multiplier_bank, g_hat, broadcast)
        y = jnp.fft.irfftn(flat, s=(plan.grid_size,) * d,
                           axes=tuple(range(d)))
        return y.astype(g.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _diff_pipeline_columns(plan: NfftPlan, backend: str | None,
                           multiplier_half: Array, src: WindowGeometry,
                           tgt: WindowGeometry, xb: Array) -> Array:
    return window_gather(
        plan, tgt,
        _spectral_mid(plan, multiplier_half,
                      window_spread(plan, src, xb, backend=backend)),
        backend=backend)


def _diff_pipeline_columns_fwd(plan, backend, multiplier_half, src, tgt, xb):
    g = window_spread(plan, src, xb, backend=backend)
    y, mid_pull = jax.vjp(
        lambda m, gg: _spectral_mid(plan, m, gg), multiplier_half, g)
    out = window_gather(plan, tgt, y, backend=backend)
    return out, (mid_pull, src, tgt)


def _diff_pipeline_columns_bwd(plan, backend, res, ybar):
    mid_pull, src, tgt = res
    v = window_spread(plan, tgt, ybar, backend=backend)  # gather-adjoint
    mult_bar, g_bar = mid_pull(v)
    x_bar = window_gather(plan, src, g_bar, backend=backend)  # spread-adjoint
    return mult_bar, None, None, x_bar


_diff_pipeline_columns.defvjp(_diff_pipeline_columns_fwd,
                              _diff_pipeline_columns_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _diff_pipeline_bank_columns(plan: NfftPlan, backend: str | None,
                                broadcast: bool, multiplier_bank: Array,
                                src: WindowGeometry, tgt: WindowGeometry,
                                xb: Array) -> Array:
    return window_gather(
        plan, tgt,
        _bank_spectral_mid(plan, broadcast, multiplier_bank,
                           window_spread(plan, src, xb, backend=backend)),
        backend=backend)


def _diff_pipeline_bank_columns_fwd(plan, backend, broadcast,
                                    multiplier_bank, src, tgt, xb):
    g = window_spread(plan, src, xb, backend=backend)
    y, mid_pull = jax.vjp(
        lambda m, gg: _bank_spectral_mid(plan, broadcast, m, gg),
        multiplier_bank, g)
    out = window_gather(plan, tgt, y, backend=backend)
    return out, (mid_pull, src, tgt)


def _diff_pipeline_bank_columns_bwd(plan, backend, broadcast, res, ybar):
    mid_pull, src, tgt = res
    v = window_spread(plan, tgt, ybar, backend=backend)
    bank_bar, g_bar = mid_pull(v)
    x_bar = window_gather(plan, src, g_bar, backend=backend)
    return bank_bar, None, None, x_bar


_diff_pipeline_bank_columns.defvjp(_diff_pipeline_bank_columns_fwd,
                                   _diff_pipeline_bank_columns_bwd)


def fused_pipeline(plan: NfftPlan, multiplier_half: Array,
                   src: WindowGeometry, tgt: WindowGeometry, x: Array,
                   spectral_reduce=None, backend: str | None = None,
                   spectral_op=None, grid_hook=None) -> Array:
    """spread -> rfftn -> multiply -> irfftn -> gather, one traceable body.

    Two hooks let the distributed matvec reuse this single implementation
    (so the local and distributed pipelines cannot drift apart):

    * ``spectral_reduce`` is applied to the support block of the multiplied
      half-spectrum (see :func:`spectral_support`) — the psum spectral mode's
      one cross-shard accumulation.
    * ``spectral_op``, when given, replaces the whole rfftn -> multiply ->
      irfftn mid-section: it maps the spread grid ``(M,)*d + (C,)`` (real,
      FFT order) to the inverse-transformed grid of the same shape.  The
      pencil spectral mode uses it to run the reduce-scattered, slab-sharded
      transform of :mod:`repro.dist.pencil_fft`; ``multiplier_half`` and
      ``spectral_reduce`` are ignored in that case (the op owns the
      multiply).

    ``backend`` selects the window-step backend (see :func:`resolve_backend`).
    ``grid_hook``, when given, maps the spread grid ``(M,)*d + (C,)`` to a
    grid of the same shape before the spectral section — the deterministic
    fault-injection seam (:mod:`repro.runtime.faultinject` poisons it to
    model grid memory corruption); production callers leave it ``None``.

    With no hooks this routes through the custom-VJP differentiable core:
    gradients flow to ``x`` and ``multiplier_half`` via the closed-form
    transpose pipeline (one extra pass), never through the window scatter
    loops.  The hooked (distributed / fault-injected) variants stay
    forward-only.
    """
    d = plan.d
    batched = x.ndim == 2
    xb = x if batched else x[:, None]
    if spectral_reduce is None and spectral_op is None and grid_hook is None:
        out = _diff_pipeline_columns(plan, backend, multiplier_half,
                                     src, tgt, xb)
        return out if batched else out[..., 0]
    g = window_spread(plan, src, xb, backend=backend)
    if grid_hook is not None:
        g = grid_hook(g)
    with jax.named_scope(scopes.FFT_MID):
        if spectral_op is not None:
            y = spectral_op(g)
        else:
            g_hat = jnp.fft.rfftn(g, axes=tuple(range(d)))
            g_hat = g_hat * multiplier_half.astype(g_hat.dtype)[..., None]
            if spectral_reduce is not None:
                sup = jnp.meshgrid(*spectral_support(plan), indexing="ij")
                block = spectral_reduce(g_hat[tuple(sup)])
                g_hat = jnp.zeros_like(g_hat).at[tuple(sup)].set(block)
            y = jnp.fft.irfftn(g_hat, s=(plan.grid_size,) * d,
                               axes=tuple(range(d)))
    out = window_gather(plan, tgt, y.astype(xb.dtype), backend=backend)
    return out if batched else out[..., 0]


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_matvec_tilde(plan: NfftPlan, multiplier_half: Array,
                       src: WindowGeometry, tgt: WindowGeometry,
                       x: Array, backend: str | None = None) -> Array:
    """y = W̃ x via the fused pipeline; x: (n,) or (n, C) real."""
    return fused_pipeline(plan, multiplier_half, src, tgt, x, backend=backend)


# ---------------------------------------------------------------------------
# Multiplier banks: amortize spread + forward FFT across S operators.
# ---------------------------------------------------------------------------

def stack_multipliers(plan: NfftPlan, b_hats) -> Array:
    """Stack per-member fused multipliers into an ``(S,) + half-spectrum`` bank.

    All members share the plan (and hence the window geometry): only the
    kernel Fourier coefficients differ, so a whole bank of operators can ride
    on one spread and one forward transform (:func:`fused_pipeline_bank`).
    """
    return jnp.stack([fused_spectral_multiplier(plan, bh) for bh in b_hats])


def fused_pipeline_bank(plan: NfftPlan, multiplier_bank: Array,
                        src: WindowGeometry, tgt: WindowGeometry, x: Array,
                        spectral_reduce=None, backend: str | None = None,
                        spectral_op=None) -> Array:
    """Bank matvec: one spread + one forward rfftn shared by S multipliers.

    ``multiplier_bank`` has shape ``(S,) + (M,)*(d-1) + (M//2+1,)`` (see
    :func:`stack_multipliers`).  Two input flavors, distinguished by rank:

    * **broadcast** — ``x`` of shape (n,) or (n, C): every member is applied
      to the same right-hand sides.  The spread and forward rfftn run once
      with C channels; the S cheap diagonal multiplies, one *batched* irfftn
      over S*C channels, and one gather with S*C channels produce
      ``(S, n)`` / ``(S, n, C)``.  An S-point multiplier sweep costs ~one
      matvec plus S spectral multiplies instead of S full pipelines.

    * **lockstep** — ``x`` of shape (S, n, C): member ``s`` is applied to
      ``x[s]`` (the shape a bank Krylov solver iterates on).  The S*C system
      columns ride the channel axis end to end — still exactly one spread,
      one forward rfftn, one irfftn, one gather.

    ``spectral_reduce`` / ``spectral_op`` mirror :func:`fused_pipeline`:
    the reduce hits the support block of the multiplied half-spectrum with
    the bank stacked into the channel axis (the distributed psum mode's one
    collective); ``spectral_op``, when given, replaces the whole rfftn ->
    multiply -> irfftn mid-section and must map the spread grid to an
    inverse-transformed grid with ``S*C`` trailing channels (it owns the
    bank multiply — the pencil mode's per-device multiplier slabs).
    """
    nb = multiplier_bank.shape[0]
    lockstep = x.ndim == 3
    if lockstep:
        if x.shape[0] != nb:
            raise ValueError(
                f"lockstep x has bank axis {x.shape[0]}, bank has {nb}")
        c = x.shape[-1]
        xb = jnp.moveaxis(x, 0, 1).reshape(x.shape[1], nb * c)
    else:
        batched = x.ndim == 2
        xb = x if batched else x[:, None]
        c = xb.shape[-1]
    out = _bank_columns_core(plan, multiplier_bank, src, tgt, xb,
                             broadcast=not lockstep,
                             spectral_reduce=spectral_reduce,
                             backend=backend, spectral_op=spectral_op)
    out = jnp.moveaxis(out.reshape(out.shape[0], nb, c), 0, 1)  # (S, n, C)
    if lockstep:
        return out
    return out if batched else out[..., 0]


def _bank_columns_transform(plan: NfftPlan, multiplier_bank: Array,
                            src: WindowGeometry, xb: Array,
                            *, broadcast: bool, spectral_reduce=None,
                            backend: str | None = None,
                            spectral_op=None) -> Array:
    """Gather-free half of the bank pipeline: spread -> rfftn -> multiply ->
    irfftn, returning the inverse-transformed grid (FFT order).

    ``xb`` is (n, K): the spread/FFT channel lanes.  ``broadcast=True``
    treats all K columns as shared right-hand sides and expands them
    against every member (output K*S channels, S-major); ``broadcast=False``
    treats K = S*C bank-major lockstep columns (column ``s*C + j`` belongs
    to member ``s``) and multiplies member-wise (output K channels).

    The grid this returns depends only on the source side (nodes, spectral
    multipliers, right-hand sides) — any number of target sets can be
    gathered from it afterwards (:func:`window_gather` /
    :func:`fused_gather_columns`), which is what the serving tier caches
    per (model, dual-vector) column.
    """
    d = plan.d
    g = window_spread(plan, src, xb, backend=backend)
    with jax.named_scope(scopes.FFT_MID):
        if spectral_op is not None:
            # (M,)*d + (S*C,): the op owns the bank multiply
            y = spectral_op(g)
        else:
            g_hat = jnp.fft.rfftn(g, axes=tuple(range(d)))
            flat = _bank_multiply(plan, multiplier_bank, g_hat, broadcast)
            if spectral_reduce is not None:
                sup = jnp.meshgrid(*spectral_support(plan), indexing="ij")
                block = spectral_reduce(flat[tuple(sup)])
                flat = jnp.zeros_like(flat).at[tuple(sup)].set(block)
            y = jnp.fft.irfftn(flat, s=(plan.grid_size,) * d,
                               axes=tuple(range(d)))
        return y.astype(xb.dtype)


def _bank_columns_core(plan: NfftPlan, multiplier_bank: Array,
                       src: WindowGeometry, tgt: WindowGeometry, xb: Array,
                       *, broadcast: bool, spectral_reduce=None,
                       backend: str | None = None, spectral_op=None) -> Array:
    """Full bank pipeline body in flat column layout (transform + gather).

    Hook-free calls route through the custom-VJP differentiable bank core
    (gradients to ``multiplier_bank`` and ``xb`` via the transpose
    pipeline); the distributed variants stay forward-only.
    """
    if spectral_reduce is None and spectral_op is None:
        return _diff_pipeline_bank_columns(plan, backend, broadcast,
                                           multiplier_bank, src, tgt, xb)
    y = _bank_columns_transform(plan, multiplier_bank, src, xb,
                                broadcast=broadcast,
                                spectral_reduce=spectral_reduce,
                                backend=backend, spectral_op=spectral_op)
    return window_gather(plan, tgt, y, backend=backend)


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_transform_columns(plan: NfftPlan, multiplier_columns: Array,
                            src: WindowGeometry, xb: Array,
                            backend: str | None = None) -> Array:
    """Per-column transform-to-grid: column ``j`` of ``xb`` (n, K) through
    multiplier ``j`` of ``multiplier_columns`` ((K,) + half-spectrum) ->
    grid ``(M,)*d + (K,)`` (real, FFT order).

    One spread + one forward rfftn + one batched irfftn for all K columns;
    the result is the gather-ready state of the prediction pipeline, so a
    serving tick that caches it per (model, dual-vector) column pays only
    a target-geometry build and one packed gather per tick
    (:func:`fused_gather_columns`).
    """
    return _bank_columns_transform(plan, multiplier_columns, src, xb,
                                   broadcast=False, backend=backend)


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_gather_columns(plan: NfftPlan, tgt: WindowGeometry, grid: Array,
                         col_index: Array,
                         backend: str | None = None) -> Array:
    """Ragged-packed gather: row ``r`` of the packed target geometry reads
    channel ``col_index[r]`` of ``grid`` ((M,)*d + (K,)) -> (m,).

    This is how a predict tick packs many users' query points into ONE
    gather: concatenate every request's (scaled) query points into one
    target set, label each row with the grid channel of its (model,
    dual-vector) column, gather once, and split the output back per
    request on the host.
    """
    out = window_gather(plan, tgt, grid, backend=backend)  # (m, K)
    idx = col_index.astype(jnp.int32)[:, None]
    return jnp.take_along_axis(out, idx, axis=1)[:, 0]


def fused_pipeline_bank_columns(plan: NfftPlan, multiplier_bank: Array,
                                src: WindowGeometry, tgt: WindowGeometry,
                                u: Array, spectral_reduce=None,
                                backend: str | None = None,
                                spectral_op=None) -> Array:
    """Lockstep bank matvec in flat column-major layout: (n, S*C) -> same.

    Column ``s*C + j`` belongs to member ``s`` — exactly the layout the
    lockstep solvers iterate on, so a bank Krylov iteration runs with ZERO
    bank-axis transposes (the (S, n, C) flavor of
    :func:`fused_pipeline_bank` costs four (n, S*C)-sized copies per call
    just moving the bank axis in and out).
    """
    nb = multiplier_bank.shape[0]
    if u.ndim != 2 or u.shape[-1] % nb:
        raise ValueError(
            f"columns input must be (n, S*C) with S={nb}, got {u.shape}")
    return _bank_columns_core(plan, multiplier_bank, src, tgt, u,
                              broadcast=False,
                              spectral_reduce=spectral_reduce,
                              backend=backend, spectral_op=spectral_op)


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_matvec_tilde_bank(plan: NfftPlan, multiplier_bank: Array,
                            src: WindowGeometry, tgt: WindowGeometry,
                            x: Array, backend: str | None = None) -> Array:
    """y[s] = W̃_s x (broadcast) or W̃_s x[s] (lockstep); see
    :func:`fused_pipeline_bank`."""
    return fused_pipeline_bank(plan, multiplier_bank, src, tgt, x,
                               backend=backend)


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_matvec_tilde_bank_columns(plan: NfftPlan, multiplier_bank: Array,
                                    src: WindowGeometry,
                                    tgt: WindowGeometry, u: Array,
                                    backend: str | None = None) -> Array:
    """Jitted :func:`fused_pipeline_bank_columns` (the solver hot loop)."""
    return fused_pipeline_bank_columns(plan, multiplier_bank, src, tgt, u,
                                       backend=backend)
