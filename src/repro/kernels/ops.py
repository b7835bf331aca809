"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True on non-TPU backends so the same call sites work
on CPU (kernel body executed in Python) and TPU (Mosaic lowering).  Interpret
mode is a CPU path only: asking for it on TPU raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import kernel_matvec as _km
from repro.kernels import nfft_window as _nw

Array = jax.Array


def _interpret(interpret: bool | None) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is a CPU path; on TPU the "
                         "kernels run compiled")
    return not on_tpu if interpret is None else interpret


def kernel_matvec(points_out: Array, points_in: Array, x: Array, *,
                  kernel_name: str = "gaussian", param: float = 1.0,
                  zero_diagonal: bool = True, tile_j: int | None = None,
                  tile_i: int | None = None,
                  interpret: bool | None = None) -> Array:
    kw = {}
    if tile_j is not None:
        kw["tile_j"] = tile_j
    if tile_i is not None:
        kw["tile_i"] = tile_i
    return _km.kernel_matvec(
        points_out, points_in, x, kernel_name=kernel_name, param=param,
        zero_diagonal=zero_diagonal,
        interpret=_interpret(interpret),
        **kw)


def window_gather(grid: Array, base: Array, weights: Array, *,
                  interpret: bool | None = None, **kw) -> Array:
    """Separable-geometry window gather; see repro.kernels.nfft_window."""
    return _nw.window_gather(
        grid, base, weights,
        interpret=_interpret(interpret),
        **kw)


def window_spread(x: Array, base: Array, weights: Array, *, padded_size: int,
                  interpret: bool | None = None, **kw) -> Array:
    """Separable-geometry window spread; see repro.kernels.nfft_window."""
    return _nw.window_spread(
        x, base, weights, padded_size=padded_size,
        interpret=_interpret(interpret),
        **kw)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = False,
                    scale: float | None = None,
                    interpret: bool | None = None, **kw) -> Array:
    return _fa.flash_attention(
        q, k, v, causal=causal, scale=scale,
        interpret=_interpret(interpret),
        **kw)
