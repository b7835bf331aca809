"""NFFT window spreading/gathering — streaming tiled Pallas backend.

The O(taps^d n) window step of the NFFT, operating directly on the fused
engine's *separable* window geometry (:class:`repro.core.nfft.
WindowGeometry`): per-node patch corner ``base`` (n, d) in padded-grid
coordinates and per-dimension weights (n, d, taps).  The tensor product
across dimensions is computed in registers inside the kernel — the
``(n, taps^d, C)`` update cube of the whole-window XLA path is never
materialized.

* spread:  Morton-sorted node tiles stream through the kernel while the
  wrap-padded oversampled grid stays resident as the kernel's revisited
  output block.  Each node scatter-adds its ``(taps,)^d`` window into only
  the grid patch it touches, via dynamic-slice read-modify-write; Morton
  order makes consecutive patches overlap, so the RMW traffic stays in
  VMEM-local lines.

* gather:  the exact transpose — each node reads its ``(taps,)^d`` patch
  out of the resident grid and contracts it with the in-register weights.

Lane-dense layout.  The resident grid block (:func:`grid_block_shape`)
puts the last spatial axis on the 128 lanes, padded to
``L = round_up(P, 128)``, and the one before it (d >= 2) on the
sublanes: ``(C,) + (P,)*(d-2) + (L // 128, P, 128)``, its lane tiles
ahead of the sublane axis because Mosaic reads at a dynamic sublane
offset only from an array one lane tile wide; ``(C, L)`` for d = 1, the
channels on the sublanes.  The channels and any further spatial axis are
leading indices.  Per node, the last axis's weights are placed on their
lanes in-register (a lane iota against the node's corner), the
second-to-last axis's weights on the sublanes of a ``(taps, 128)`` slab,
and every leading (channel, x-plane) index and lane tile updates or reads
one such slab at a dynamic sublane offset.  :func:`to_grid_block` /
:func:`from_grid_block` convert between this layout and the engine's
``(P,)*d + (C,)`` grids.

Node corners and weights are read as scalars from SMEM, one block per
node tile with the nodes on its minor axis; node values come in a VMEM
tile.  The loop runs :data:`_NODE_UNROLL` nodes per step.  The gather
keeps one 128-lane partial sum per node and channel and reduces them
across lanes once per tile.  ``d`` is 1..3 (the paper's range); the grid
is the *padded* grid (``repro.core.nfft.padded_grid_size``) so no wrapping
logic lives in the kernel — the fold-back of the periodic pad is the
caller's job.

VMEM: the whole grid block is resident, single-buffered (its block index
never changes).  :func:`grid_fits_vmem` is the one rule for whether a grid
may stay resident, and :func:`channels_per_call` how many channels of it
one call may hold; each call asks Mosaic for exactly the VMEM its blocks
need (:func:`_vmem_limit`).
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_NODE_TILE = 1024

_SUBLANES, _LANES = 8, 128
# Largest resident grid block and gather rows, in VMEM bytes after tiling.
# A TPU v5e core has 128 MiB of VMEM; half of it goes to those, the rest
# to the double-buffered node tiles and Mosaic's own scratch.  Lane-dense,
# Fig. 5's d=3 grid (36^3, N=16 m=2) takes 0.74 MB per channel, the d=3
# SETUP_2 grid (72^3) 2.65 MB, and the rows 0.52 MB at the default tile.
VMEM_GRID_BUDGET = 64 * 2 ** 20
_VMEM_HEADROOM = 4 * 2 ** 20
# Nodes per loop step: the scheduler overlaps one node's scalar reads and
# weight placement with the next one's slab traffic (on a v5e, 8 nodes
# per step run the window step 2-3x faster than one).
_NODE_UNROLL = 8


def _round_up(v: int, k: int) -> int:
    return -(-v // k) * k


def vmem_bytes(shape) -> int:
    """VMEM bytes of a block of 32-bit values: its two minor dims tile as
    (8, 128) words."""
    *lead, sub, lane = shape
    return (math.prod(lead) * _round_up(sub, _SUBLANES)
            * _round_up(lane, _LANES) * 4)


def grid_block_shape(padded_size: int, d: int, channels: int) -> tuple:
    """The kernels' resident grid layout (module docstring): ``(C, L)`` for
    d = 1, ``(C,) + (P,)*(d-2) + (L // 128, P, 128)`` for d >= 2."""
    lanes = _round_up(padded_size, _LANES)
    if d == 1:
        return (channels, lanes)
    return ((channels,) + (padded_size,) * (d - 2)
            + (lanes // _LANES, padded_size, _LANES))


def grid_fits_vmem(padded_size: int, d: int, channels: int) -> bool:
    """Whether a float32 call on ``channels`` channels may keep its grid
    resident in VMEM (the kernels' one limit): the grid block and the
    gather's per-node rows at the default node tile (:func:`_vmem_limit`)
    within the budget."""
    return (vmem_bytes(grid_block_shape(padded_size, d, channels))
            + vmem_bytes(_rows_shape(DEFAULT_NODE_TILE, channels))
            <= VMEM_GRID_BUDGET)


def channels_per_call(padded_size: int, d: int, channels: int) -> int:
    """Channels one kernel call holds, so that ``channels`` split into as
    few calls of near-equal width as fit :func:`grid_fits_vmem`; 0 when
    not even one channel fits."""
    if not grid_fits_vmem(padded_size, d, 1):
        return 0
    fit = 1  # the block grows monotonically with the channel count
    while fit < channels and grid_fits_vmem(padded_size, d, fit + 1):
        fit += 1
    return -(-channels // -(-channels // fit))


def to_grid_block(grid: Array, d: int) -> Array:
    """``(P,)*d + (C,)`` grid -> the kernels' block (lanes ``P..L`` zero)."""
    p = grid.shape[0]
    lanes = _round_up(p, _LANES)
    block = jnp.pad(jnp.moveaxis(grid, -1, 0),
                    [(0, 0)] * d + [(0, lanes - p)])
    if d == 1:
        return block
    block = block.reshape(block.shape[:-1] + (lanes // _LANES, _LANES))
    return jnp.swapaxes(block, -3, -2)


def from_grid_block(block: Array, padded_size: int, d: int) -> Array:
    """The kernels' block -> ``(P,)*d + (C,)`` (drops lanes ``P..L``)."""
    if d > 1:
        block = jnp.swapaxes(block, -3, -2)
        block = block.reshape(block.shape[:-2] + (-1,))
    return jnp.moveaxis(block[..., :padded_size], 0, -1)


def _rows_shape(tn: int, channels: int) -> tuple:
    """The gather's scratch: one 128-lane partial sum per node and channel,
    reduced across lanes once per tile."""
    return (channels, tn, _LANES)


def _vmem_limit(grid_shape, tn: int, c: int, rows: bool) -> int:
    """Scoped-VMEM request: the resident grid, the double-buffered
    node-value tile (corners and weights live in SMEM) and, for the
    gather, its rows."""
    extra = vmem_bytes(_rows_shape(tn, c)) if rows else 0
    return (vmem_bytes(grid_shape) + 2 * vmem_bytes((tn, c)) + extra
            + _VMEM_HEADROOM)


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


def _node_tiles(base: Array, weights: Array, tn: int):
    """Corners ``(d, n)`` and weights ``(d * taps, n)``, nodes on the minor
    axis and padded to whole tiles, with their SMEM block specs (one node
    tile per grid step).  Padded rows carry zero weights, so their windows
    add or read exact zeros at corner 0."""
    n, d, taps = weights.shape
    pad = (-n) % tn
    bt = jnp.pad(base.T, ((0, 0), (0, pad)))
    wt = jnp.pad(jnp.transpose(weights, (1, 2, 0)).reshape(d * taps, n),
                 ((0, 0), (0, pad)))
    specs = [pl.BlockSpec((d, tn), _lane_tile_map,
                          memory_space=pltpu.SMEM),
             pl.BlockSpec((d * taps, tn), _lane_tile_map,
                          memory_space=pltpu.SMEM)]
    return bt, wt, specs, (n + pad) // tn


def _lane_tile_map(j):
    """Index map of a ``(rows, node tile)`` block (see :func:`_tile_map`)."""
    return jnp.zeros_like(j), j


class _Node:
    """One node's window on the lane-dense grid block, built in-register.

    ``row`` holds the last axis's weights on their lanes, ``(1, L)``, and
    ``col`` (d >= 2) the second-to-last axis's weights on the sublanes of
    a ``(taps, 128)`` array; their product over one lane tile is the slab
    that :meth:`index` addresses.  Mosaic reads a slab at a dynamic
    sublane offset one lane tile at a time, so for d >= 2 ``tiles`` are
    the grid's 128-lane tiles; for d = 1 the row is read whole.
    :meth:`planes` lists the leading spatial indices with their scalar
    weight.
    """

    def __init__(self, base_ref, w_ref, r, *, d: int, taps: int,
                 lanes: int, dtype):
        # lanes: L, the grid's last axis padded to whole lane tiles
        i32 = jnp.int32  # Python ints lower as i64 under x64: keep int32
        b = [base_ref[i32(t), r] for t in range(d)]
        w = [[w_ref[i32(t * taps + k), r] for k in range(taps)]
             for t in range(d)]
        self.d, self.taps, self.b, self.w = d, taps, b, w
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) - b[-1]
        self.row = jnp.zeros((1, lanes), dtype)
        for k in range(taps):
            self.row = jnp.where(lane == i32(k), w[-1][k], self.row)
        self.col, self.tiles = None, [slice(None)]
        if d > 1:
            sub = jax.lax.broadcasted_iota(jnp.int32, (taps, _LANES), 0)
            self.col = jnp.zeros((taps, _LANES), dtype)
            for k in range(taps):
                self.col = jnp.where(sub == i32(k), w[-2][k], self.col)
            self.tiles = list(range(lanes // _LANES))

    def slab(self, row: Array, tile) -> Array:
        """Lane tile ``tile`` of ``row`` spread over the second-to-last
        axis's weights."""
        if self.col is None:
            return row
        return self.col * row[:, tile * _LANES:(tile + 1) * _LANES]

    def planes(self) -> list:
        """(leading spatial indices, their weight or None) of each slab."""
        out = []
        for ks in itertools.product(range(self.taps),
                                    repeat=max(self.d - 2, 0)):
            wt = None
            for t, k in enumerate(ks):
                wt = self.w[t][k] if wt is None else wt * self.w[t][k]
            out.append((tuple(self.b[t] + jnp.int32(k)
                              for t, k in enumerate(ks)), wt))
        return out

    def index(self, c: int, plane: tuple, tile):
        """Grid-block index of channel ``c``'s slab at ``plane``, ``tile``."""
        if self.d == 1:
            return (pl.ds(c, 1), tile)
        return ((c,) + plane + (tile, pl.ds(self.b[self.d - 2], self.taps),
                                slice(None)))


def _grid_lanes(block_shape, d: int) -> int:
    """L of a grid block (see :func:`grid_block_shape`)."""
    return block_shape[-1] * (block_shape[-3] if d > 1 else 1)


def _node_loop(rows: int, body) -> None:
    """Run ``body(r)`` over a tile's rows, ``_NODE_UNROLL`` rows per loop
    step, with an int32 index (a Python-int bound gives an int64 index
    under x64, which Mosaic cannot lower)."""
    unroll = math.gcd(_NODE_UNROLL, rows)

    def step(i, carry):
        for u in range(unroll):
            body(i * jnp.int32(unroll) + jnp.int32(u))
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(rows // unroll), step,
                      jnp.int32(0))


def _spread_kernel(base_ref, w_ref, x_ref, o_ref, *, d: int, taps: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    c_all = x_ref.shape[1]
    lanes = _grid_lanes(o_ref.shape, d)

    def body(r):
        node = _Node(base_ref, w_ref, r, d=d, taps=taps, lanes=lanes,
                     dtype=o_ref.dtype)
        planes = node.planes()
        xr = x_ref[pl.ds(r, 1), :]  # (1, C)
        for c in range(c_all):
            # Mosaic broadcasts along lanes and sublanes in two steps
            xc = node.row * jnp.broadcast_to(xr[:, c:c + 1], (1, lanes))
            for tile in node.tiles:
                patch = node.slab(xc, tile)
                idxs = [node.index(c, plane, tile) for plane, _ in planes]
                # the planes are distinct: read every slab before writing
                # any, so the loads issue back to back
                olds = [o_ref[idx] for idx in idxs]
                for idx, old, (_, wt) in zip(idxs, olds, planes):
                    o_ref[idx] = old + (patch if wt is None else patch * wt)

    _node_loop(x_ref.shape[0], body)


@functools.partial(jax.jit, static_argnames=("padded_size", "node_tile",
                                             "interpret"))
def window_spread(x: Array, base: Array, weights: Array, *, padded_size: int,
                  node_tile: int = DEFAULT_NODE_TILE,
                  interpret: bool = False) -> Array:
    """Scatter-add separable node windows onto the padded grid.

    x: (n,) or (n, C); base: (n, d) int32 patch corners with
    ``0 <= base`` and ``base + taps <= padded_size``; weights: (n, d, taps).
    Returns the grid block :func:`grid_block_shape` (without its channel
    axis for 1-D ``x``); lanes ``padded_size..L`` stay zero.
    """
    n, d, taps = weights.shape
    batched = x.ndim == 2
    x2 = x if batched else x[:, None]
    c = x2.shape[1]
    tn = min(node_tile, _round_up(n, _SUBLANES))
    bp, wp, specs, tiles = _node_tiles(base, weights, tn)
    xp = jnp.pad(x2, ((0, tiles * tn - n), (0, 0)))
    grid_shape = grid_block_shape(padded_size, d, c)

    out = pl.pallas_call(
        functools.partial(_spread_kernel, d=d, taps=taps),
        grid=(tiles,),
        in_specs=specs + [pl.BlockSpec((tn, c), _tile_map(2))],
        out_specs=pl.BlockSpec(grid_shape, _resident_map(len(grid_shape)),
                               pipeline_mode=pl.Buffered(1)),
        out_shape=jax.ShapeDtypeStruct(grid_shape, x2.dtype),
        # every tile accumulates into the one resident grid: sequential
        compiler_params=_compiler_params(
            "arbitrary", _vmem_limit(grid_shape, tn, c, rows=False)),
        interpret=interpret,
    )(bp, wp, xp)
    return out if batched else out[0]


def _gather_kernel(g_ref, base_ref, w_ref, o_ref, rows_ref, *, d: int,
                   taps: int):
    c_all = o_ref.shape[1]
    lanes = _grid_lanes(g_ref.shape, d)

    def body(r):
        node = _Node(base_ref, w_ref, r, d=d, taps=taps, lanes=lanes,
                     dtype=o_ref.dtype)
        planes = node.planes()
        for c in range(c_all):
            total = None
            for tile in node.tiles:
                acc = None
                for plane, wt in planes:
                    slab = g_ref[node.index(c, plane, tile)]
                    slab = slab if wt is None else slab * wt
                    acc = slab if acc is None else acc + slab
                part = jnp.sum(acc * node.slab(node.row, tile), axis=0,
                               keepdims=True)
                total = part if total is None else total + part
            # d = 1 reads its row whole: fold it onto one lane tile
            total = sum(total[:, q:q + _LANES]
                        for q in range(0, total.shape[1], _LANES))
            rows_ref[c, pl.ds(r, 1), :] = total

    _node_loop(o_ref.shape[0], body)
    # one lane reduction per tile and channel, not one per node
    for c in range(c_all):
        o_ref[:, c:c + 1] = jnp.sum(rows_ref[c], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("node_tile", "interpret"))
def window_gather(grid: Array, base: Array, weights: Array, *,
                  node_tile: int = DEFAULT_NODE_TILE,
                  interpret: bool = False) -> Array:
    """Gather separable node windows from the padded grid (spread transpose).

    grid: the block :func:`grid_block_shape`, with or without its channel
    axis; base/weights as in :func:`window_spread`.  Returns (n, C), or
    (n,) for a grid without channel axis.
    """
    n, d, taps = weights.shape
    batched = grid.ndim == len(grid_block_shape(1, d, 1))
    g2 = grid if batched else grid[None]
    c = g2.shape[0]
    tn = min(node_tile, _round_up(n, _SUBLANES))
    bp, wp, specs, tiles = _node_tiles(base, weights, tn)

    out = pl.pallas_call(
        functools.partial(_gather_kernel, d=d, taps=taps),
        scratch_shapes=[pltpu.VMEM(_rows_shape(tn, c), g2.dtype)],
        grid=(tiles,),
        in_specs=[pl.BlockSpec(g2.shape, _resident_map(g2.ndim),
                               pipeline_mode=pl.Buffered(1))] + specs,
        out_specs=pl.BlockSpec((tn, c), _tile_map(2)),
        out_shape=jax.ShapeDtypeStruct((tiles * tn, c), g2.dtype),
        compiler_params=_compiler_params(
            "parallel", _vmem_limit(g2.shape, tn, c, rows=True)),
        interpret=interpret,
    )(g2, bp, wp)
    out = out[:n]
    return out if batched else out[:, 0]
