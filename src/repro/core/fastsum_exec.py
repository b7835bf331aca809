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


def _xla_gather(plan: NfftPlan, geometry: WindowGeometry,
                gpad: Array) -> Array:
    """Streaming tiled whole-window gather (transpose of :func:`_xla_spread`).

    One `lax.gather` of (taps,)^d + (C,) window slices per node tile,
    contracted with the tile's weight cube; node values come out (n, C) in
    row (Morton) order.
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
# The pipeline on flat columns.
#
# Every entry point runs one core (:func:`_fused_columns`) on node columns
# ``xb`` (n, K) against a bank of S multipliers (stacked ``(S,) +
# half-spectrum`` by :func:`stack_multipliers`, or a sequence of S), in one
# of two layouts:
#
#     broadcast:  the K = C columns are right-hand sides that every member
#                 is applied to; S*C columns come out, member-major (column
#                 s*C + j is member s applied to column j);
#     lockstep:   the K = S*C columns are member-major already, and member s
#                 is applied to its own C of them (the shape a bank Krylov
#                 solver iterates on).
#
# Either way one spread and one forward rfftn serve all S members.  A single
# operator is the broadcast bank of one, the sequence of its multiplier:
# S = 1, C columns in and C out.
#
# Differentiable core (custom VJP).  The pipeline is linear in both x and
# the spectral multipliers, and window_spread / window_gather are exact
# mutual adjoints on a shared geometry (same base/weights/perm; verified to
# 1e-12 by the adjoint test suite).  That gives the whole matvec a
# closed-form transpose that never differentiates *through* the fori_loop
# scatter tiles or the Pallas kernels:
#
#     cotangent wrt x:  spread ybar on the TARGET geometry (gather-adjoint),
#                       run the adjoint spectral mid-section, gather on the
#                       SOURCE geometry — one extra pipeline pass;
#     cotangent wrt the multipliers:  elementwise product of the forward
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
# distributed hooks (spectral_reduce / spectral_op) are differentiated
# inside the mid-section's jax.vjp, so a hook takes the multipliers as an
# argument and closes over no traced value.
# ---------------------------------------------------------------------------

def stack_multipliers(plan: NfftPlan, b_hats) -> Array:
    """Stack per-member fused multipliers into an ``(S,) + half-spectrum`` bank.

    All members share the plan (and hence the window geometry): only the
    kernel Fourier coefficients differ, so a whole bank of operators can ride
    on one spread and one forward transform (:func:`fused_pipeline_bank`).
    """
    return jnp.stack([fused_spectral_multiplier(plan, bh) for bh in b_hats])


def bank_multiply(multiplier_bank, g_hat: Array, broadcast: bool) -> Array:
    """Member-wise spectral multiply -> the (..., S*C) product, member-major.

    ``g_hat`` has its spectrum axes leading and its channels in the layout
    ``broadcast`` names (see above).  ``multiplier_bank`` holds S
    multipliers over the same spectrum axes (the whole half-spectrum, or
    one device's pencil slab of it, :mod:`repro.dist.fastsum_dist`):
    stacked ``(S,) + spectrum``, or a sequence.  A single operator passes
    the sequence of its one multiplier, and the product is then exactly
    ``g_hat * multiplier[..., None]``, with no layout op on either side: it
    compiles to the program of a lone multiply.
    """
    nb = len(multiplier_bank)
    if not broadcast and g_hat.shape[-1] % nb:
        raise ValueError(f"lockstep columns must be S*C with S={nb}, "
                         f"got {g_hat.shape[-1]}")
    c = g_hat.shape[-1] if broadcast else g_hat.shape[-1] // nb
    return jnp.concatenate(
        [(g_hat if broadcast else g_hat[..., s * c:(s + 1) * c])
         * multiplier_bank[s].astype(g_hat.dtype)[..., None]
         for s in range(nb)], axis=-1)


def _spectral_mid(plan: NfftPlan, multiplier_bank, broadcast: bool,
                  g: Array, spectral_reduce=None, spectral_op=None) -> Array:
    """The mid-section on the spread grid ``(M,)*d + (K,)`` -> the
    inverse-transformed grid with the output's columns (FFT order).

    rfftn -> :func:`bank_multiply` -> irfftn, with ``spectral_reduce``
    applied to the support block of the multiplied half-spectrum
    (:func:`spectral_support`) when given; or
    ``spectral_op(g, multiplier_bank)`` in place of the whole section.
    """
    d = plan.d
    with jax.named_scope(scopes.FFT_MID):
        if spectral_op is not None:
            y = spectral_op(g, multiplier_bank)
        else:
            g_hat = jnp.fft.rfftn(g, axes=tuple(range(d)))
            g_hat = bank_multiply(multiplier_bank, g_hat, broadcast)
            if spectral_reduce is not None:
                sup = jnp.meshgrid(*spectral_support(plan), indexing="ij")
                block = spectral_reduce(g_hat[tuple(sup)])
                g_hat = jnp.zeros_like(g_hat).at[tuple(sup)].set(block)
            y = jnp.fft.irfftn(g_hat, s=(plan.grid_size,) * d,
                               axes=tuple(range(d)))
        return y.astype(g.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _fused_columns(plan: NfftPlan, backend: str | None, broadcast: bool,
                   spectral_reduce, spectral_op, multiplier_bank,
                   src: WindowGeometry, tgt: WindowGeometry,
                   xb: Array) -> Array:
    """spread -> rfftn -> multiply -> irfftn -> gather on flat columns.

    ``xb`` (n, K) in the layout ``broadcast`` names (see above); returns
    (n_tgt, S*C).  Two hooks let the distributed matvec reuse this single
    implementation (so the local and distributed pipelines cannot drift
    apart):

    * ``spectral_reduce`` is applied to the support block of the multiplied
      half-spectrum (see :func:`spectral_support`) — the psum spectral
      mode's one cross-shard accumulation.
    * ``spectral_op``, when given, replaces the whole rfftn -> multiply ->
      irfftn mid-section: it maps the spread grid ``(M,)*d + (K,)`` (real,
      FFT order) and ``multiplier_bank`` to the inverse-transformed grid
      with ``S*C`` channels.  The pencil spectral mode uses it to run the
      reduce-scattered, slab-sharded transform of
      :mod:`repro.dist.pencil_fft`; the op owns the multiply, and
      ``spectral_reduce`` is ignored.

    Gradients flow to ``xb`` and ``multiplier_bank`` via the closed-form
    transpose pipeline (one extra pass), never through the window scatter
    loops.
    """
    g = window_spread(plan, src, xb, backend=backend)
    y = _spectral_mid(plan, multiplier_bank, broadcast, g,
                      spectral_reduce=spectral_reduce,
                      spectral_op=spectral_op)
    return window_gather(plan, tgt, y, backend=backend)


def _fused_columns_fwd(plan, backend, broadcast, spectral_reduce,
                       spectral_op, multiplier_bank, src, tgt, xb):
    g = window_spread(plan, src, xb, backend=backend)
    y, mid_pull = jax.vjp(
        lambda m, gg: _spectral_mid(plan, m, broadcast, gg,
                                    spectral_reduce=spectral_reduce,
                                    spectral_op=spectral_op),
        multiplier_bank, g)
    out = window_gather(plan, tgt, y, backend=backend)
    return out, (mid_pull, src, tgt)


def _fused_columns_bwd(plan, backend, broadcast, spectral_reduce,
                       spectral_op, res, ybar):
    mid_pull, src, tgt = res
    v = window_spread(plan, tgt, ybar, backend=backend)  # gather-adjoint
    bank_bar, g_bar = mid_pull(v)
    x_bar = window_gather(plan, src, g_bar, backend=backend)  # spread-adjoint
    return bank_bar, None, None, x_bar


_fused_columns.defvjp(_fused_columns_fwd, _fused_columns_bwd)


def fused_pipeline(plan: NfftPlan, multiplier_half: Array,
                   src: WindowGeometry, tgt: WindowGeometry, x: Array,
                   spectral_reduce=None, backend: str | None = None,
                   spectral_op=None) -> Array:
    """One operator, ``x`` (n,) or (n, C): the flat-column core as the
    broadcast bank of one (hooks and gradients as :func:`_fused_columns`;
    ``backend`` selects the window step, see :func:`resolve_backend`)."""
    xb = x if x.ndim == 2 else x[:, None]
    out = _fused_columns(plan, backend, True, spectral_reduce, spectral_op,
                         (multiplier_half,), src, tgt, xb)
    return out if x.ndim == 2 else out[..., 0]


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_matvec_tilde(plan: NfftPlan, multiplier_half: Array,
                       src: WindowGeometry, tgt: WindowGeometry,
                       x: Array, backend: str | None = None) -> Array:
    """y = W̃ x via the fused pipeline; x: (n,) or (n, C) real."""
    return fused_pipeline(plan, multiplier_half, src, tgt, x, backend=backend)


def fused_pipeline_bank(plan: NfftPlan, multiplier_bank: Array,
                        src: WindowGeometry, tgt: WindowGeometry, x: Array,
                        spectral_reduce=None, backend: str | None = None,
                        spectral_op=None) -> Array:
    """Bank matvec: one spread + one forward rfftn shared by S multipliers.

    ``multiplier_bank`` has shape ``(S,) + (M,)*(d-1) + (M//2+1,)`` (see
    :func:`stack_multipliers`).  Two input flavors, distinguished by rank:

    * **broadcast** — ``x`` of shape (n,) or (n, C): every member is applied
      to the same right-hand sides, giving ``(S, n)`` / ``(S, n, C)``.  An
      S-point multiplier sweep costs ~one matvec plus S spectral multiplies
      instead of S full pipelines.

    * **lockstep** — ``x`` of shape (S, n, C): member ``s`` is applied to
      ``x[s]``; the S*C system columns ride the channel axis end to end.

    Both are reshapes around :func:`_fused_columns` (hooks as there, with
    the bank stacked into the channel axis: the distributed psum mode's one
    collective carries all S members).
    """
    nb = multiplier_bank.shape[0]
    lockstep = x.ndim == 3
    if lockstep:
        if x.shape[0] != nb:
            raise ValueError(
                f"lockstep x has bank axis {x.shape[0]}, bank has {nb}")
        xb = jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1)
    else:
        xb = x if x.ndim == 2 else x[:, None]
    out = _fused_columns(plan, backend, not lockstep, spectral_reduce,
                         spectral_op, multiplier_bank, src, tgt, xb)
    out = jnp.moveaxis(out.reshape(out.shape[0], nb, -1), 0, 1)  # (S, n, C)
    return out if x.ndim >= 2 else out[..., 0]


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
    """Lockstep bank matvec on flat columns: (n, S*C) -> (n, S*C), column
    ``s*C + j`` member ``s``'s — the layout the lockstep solvers iterate
    on, so a bank Krylov iteration moves no bank axis."""
    return _fused_columns(plan, backend, False, None, None, multiplier_bank,
                          src, tgt, u)


@functools.partial(jax.jit, static_argnames=("plan", "backend"))
def fused_transform_columns(plan: NfftPlan, multiplier_columns: Array,
                            src: WindowGeometry, xb: Array,
                            backend: str | None = None) -> Array:
    """Per-column transform-to-grid: column ``j`` of ``xb`` (n, K) through
    multiplier ``j`` of ``multiplier_columns`` ((K,) + half-spectrum) ->
    grid ``(M,)*d + (K,)`` (real, FFT order).

    The core's gather-free half in lockstep layout (S = K, C = 1): one
    spread + one forward rfftn + one batched irfftn for all K columns.  The
    grid depends only on the source side, so any number of target sets can
    be gathered from it afterwards: a serving tick that caches it per
    (model, dual-vector) column pays only a target-geometry build and one
    packed gather per tick (:func:`fused_gather_columns`).
    """
    g = window_spread(plan, src, xb, backend=backend)
    return _spectral_mid(plan, multiplier_columns, False, g)


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
