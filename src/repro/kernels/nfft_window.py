"""NFFT window spreading/gathering — streaming tiled Pallas backend.

The O(taps^d n) window step of the NFFT, operating directly on the fused
engine's *separable* window geometry (:class:`repro.core.nfft.
WindowGeometry`): per-node patch corner ``base`` (n, d) in padded-grid
coordinates and per-dimension weights (n, d, taps).  The tensor product
across dimensions is computed in registers inside the kernel — the
``(n, taps^d, C)`` update cube of the whole-window XLA path is never
materialized.

* spread:  Morton-sorted node tiles stream through VMEM while the
  wrap-padded oversampled grid stays resident as the kernel's revisited
  output block.  Each node scatter-adds its ``(taps,)^d`` window into only
  the grid patch it touches, via dynamic-slice read-modify-write; Morton
  order makes consecutive patches overlap, so the RMW traffic stays in
  cache/VMEM-local lines.

* gather:  the exact transpose — each node dynamic-slices its ``(taps,)^d``
  patch out of the resident grid and contracts it with the in-register
  weight cube.

Batched channels (the fused engine's multi-RHS layout) ride on the
innermost dimension of both the grid and the node values, so one geometry
stream is amortized over C right-hand sides.  ``d`` is 1..3 (the paper's
range); the grid is the *padded* grid (``repro.core.nfft.padded_grid_size``)
so no wrapping logic lives in the kernel — the fold-back of the periodic pad
is the caller's (cheap, backend-independent) job.

VMEM: the whole padded grid is one resident block, single-buffered (its
block index never changes).  On the chip the two minor dimensions of a
block tile as (8 sublanes, 128 lanes), so the channel axis pads to 128
lanes: a C = 1 grid takes 128x its logical size.  :func:`grid_fits_vmem`
is the one rule for whether a grid may stay resident; each call asks
Mosaic for exactly the VMEM its blocks need (:func:`_vmem_limit`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_NODE_TILE = 1024

_SUBLANES, _LANES = 8, 128
# Largest resident grid block, in VMEM bytes after tiling.  A TPU v5e core
# has 128 MiB of VMEM; half of it goes to the grid, the rest to the
# double-buffered node tiles (~10 MiB at the default tile) and Mosaic's own
# scratch.  Fig. 5's d=3 grid (36^3, N=16 m=2) takes 25.3 MiB and fits; the
# d=3 SETUP_2 grid (72^3) takes 182 MiB and does not.
VMEM_GRID_BUDGET = 64 * 2 ** 20
_VMEM_HEADROOM = 4 * 2 ** 20


def _round_up(v: int, k: int) -> int:
    return -(-v // k) * k


def vmem_bytes(shape) -> int:
    """VMEM bytes of a block of 32-bit values: its two minor dims tile as
    (8, 128) words."""
    *lead, sub, lane = shape
    return (math.prod(lead) * _round_up(sub, _SUBLANES)
            * _round_up(lane, _LANES) * 4)


def grid_fits_vmem(padded_size: int, d: int, channels: int) -> bool:
    """Whether the float32 ``(padded_size,)*d + (channels,)`` grid may stay
    resident in VMEM (the kernels' one limit)."""
    return vmem_bytes((padded_size,) * d + (channels,)) <= VMEM_GRID_BUDGET


def _vmem_limit(grid_shape, tn: int, d: int, taps: int, c: int) -> int:
    """Scoped-VMEM request: the resident grid plus double-buffered tiles."""
    tiles = (vmem_bytes((tn, d)) + vmem_bytes((tn, d, taps))
             + vmem_bytes((tn, c)))
    return vmem_bytes(grid_shape) + 2 * tiles + _VMEM_HEADROOM


def _tile_map(rank: int):
    """Index map of a node-tile block: tile ``j``, whole along the rest.

    The zeros take ``j``'s int32 type: a Python ``0`` becomes int64 when
    x64 is enabled, and Mosaic cannot lower an index map that returns
    mixed integer widths.
    """
    return lambda j: (j,) + (jnp.zeros_like(j),) * (rank - 1)


def _resident_map(rank: int):
    """Index map of the resident grid block (see :func:`_tile_map`)."""
    return lambda j: (jnp.zeros_like(j),) * rank


def _compiler_params(semantics: str, vmem_limit: int):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=vmem_limit)


def _weight_cube(w: Array, d: int) -> Array:
    """Tensor product of one node's per-dim weights: (d, taps) -> (taps,)*d."""
    cube = w[0]
    for t in range(1, d):
        cube = cube[..., None] * w[t]
    return cube


def _spread_kernel(base_ref, w_ref, x_ref, o_ref, *, d: int, taps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(r, carry):
        b = base_ref[pl.ds(r, 1), :][0]  # (d,) patch corner
        w = w_ref[pl.ds(r, 1)][0]  # (d, taps)
        xr = x_ref[pl.ds(r, 1), :][0]  # (C,) channels in-register
        cube = _weight_cube(w, d)  # (taps,)*d
        patch = tuple(pl.ds(b[t], taps) for t in range(d)) + (slice(None),)
        o_ref[patch] = o_ref[patch] + cube[..., None] * xr
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("padded_size", "node_tile",
                                             "interpret"))
def window_spread(x: Array, base: Array, weights: Array, *, padded_size: int,
                  node_tile: int = DEFAULT_NODE_TILE,
                  interpret: bool = False) -> Array:
    """Scatter-add separable node windows onto the padded grid.

    x: (n,) or (n, C); base: (n, d) int32 patch corners with
    ``0 <= base`` and ``base + taps <= padded_size``; weights: (n, d, taps).
    Returns the padded grid, shape ``(padded_size,)*d`` [+ ``(C,)``].
    """
    n, d, taps = weights.shape
    batched = x.ndim == 2
    x2 = x if batched else x[:, None]
    c = x2.shape[1]
    tn = min(node_tile, max(8, n))
    pad = (-n) % tn
    # padded rows carry zero weights: their windows add exact zeros
    xp = jnp.pad(x2, ((0, pad), (0, 0)))
    bp = jnp.pad(base, ((0, pad), (0, 0)))
    wp = jnp.pad(weights, ((0, pad), (0, 0), (0, 0)))
    grid_shape = (padded_size,) * d + (c,)

    out = pl.pallas_call(
        functools.partial(_spread_kernel, d=d, taps=taps),
        grid=(xp.shape[0] // tn,),
        in_specs=[
            pl.BlockSpec((tn, d), _tile_map(2)),
            pl.BlockSpec((tn, d, taps), _tile_map(3)),
            pl.BlockSpec((tn, c), _tile_map(2)),
        ],
        out_specs=pl.BlockSpec(grid_shape, _resident_map(d + 1),
                               pipeline_mode=pl.Buffered(1)),
        out_shape=jax.ShapeDtypeStruct(grid_shape, x2.dtype),
        # every tile accumulates into the one resident grid: sequential
        compiler_params=_compiler_params(
            "arbitrary", _vmem_limit(grid_shape, tn, d, taps, c)),
        interpret=interpret,
    )(bp, wp, xp)
    return out if batched else out[..., 0]


def _gather_kernel(g_ref, base_ref, w_ref, o_ref, *, d: int, taps: int):
    def body(r, carry):
        b = base_ref[pl.ds(r, 1), :][0]
        w = w_ref[pl.ds(r, 1)][0]
        cube = _weight_cube(w, d)
        patch = tuple(pl.ds(b[t], taps) for t in range(d)) + (slice(None),)
        vals = g_ref[patch]  # (taps,)*d + (C,)
        o_ref[pl.ds(r, 1), :] = jnp.sum(
            vals * cube[..., None], axis=tuple(range(d)))[None]
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("node_tile", "interpret"))
def window_gather(grid: Array, base: Array, weights: Array, *,
                  node_tile: int = DEFAULT_NODE_TILE,
                  interpret: bool = False) -> Array:
    """Gather separable node windows from the padded grid (spread transpose).

    grid: (padded_size,)*d [+ (C,)]; base/weights as in
    :func:`window_spread`.  Returns (n,) or (n, C) to match ``grid``.
    """
    n, d, taps = weights.shape
    batched = grid.ndim == d + 1
    g2 = grid if batched else grid[..., None]
    c = g2.shape[-1]
    tn = min(node_tile, max(8, n))
    pad = (-n) % tn
    bp = jnp.pad(base, ((0, pad), (0, 0)))  # padded rows read patch 0 * w=0
    wp = jnp.pad(weights, ((0, pad), (0, 0), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_gather_kernel, d=d, taps=taps),
        grid=(bp.shape[0] // tn,),
        in_specs=[
            pl.BlockSpec(g2.shape, _resident_map(d + 1),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((tn, d), _tile_map(2)),
            pl.BlockSpec((tn, d, taps), _tile_map(3)),
        ],
        out_specs=pl.BlockSpec((tn, c), _tile_map(2)),
        out_shape=jax.ShapeDtypeStruct((bp.shape[0], c), g2.dtype),
        compiler_params=_compiler_params(
            "parallel", _vmem_limit(g2.shape, tn, d, taps, c)),
        interpret=interpret,
    )(g2, bp, wp)
    out = out[:n]
    return out if batched else out[:, 0]
