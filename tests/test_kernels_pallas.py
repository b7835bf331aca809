"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fastsum_exec
from repro.core.fastsum import SETUP_2, SETUP_3, FastsumParams
from repro.core.nfft import WindowGeometry, padded_grid_size
from repro.kernels import nfft_window, ops, ref

RNG = np.random.default_rng(11)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 1e-12


# ---------------------------------------------------------------- kernel_matvec
@pytest.mark.parametrize("n,d,c", [(64, 1, 1), (200, 2, 1), (300, 3, 2),
                                   (257, 3, 1), (128, 2, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_kernel_matvec_shapes(n, d, c, dtype):
    pts = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    x = jnp.asarray(RNG.normal(size=(n, c)), dtype)
    out = ops.kernel_matvec(pts, pts, x, kernel_name="gaussian", param=1.5,
                            tile_j=64, tile_i=128, interpret=True)
    want = ref.kernel_matvec_ref(pts, pts, x, "gaussian", 1.5)
    rel = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
    assert rel < _tol(dtype), rel


@pytest.mark.parametrize("kname,param", [
    ("gaussian", 2.0), ("laplacian_rbf", 0.7),
    ("multiquadric", 1.0), ("inverse_multiquadric", 1.0)])
def test_kernel_matvec_all_kernels(kname, param):
    pts = jnp.asarray(RNG.normal(size=(200, 3)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(200,)), jnp.float32)
    out = ops.kernel_matvec(pts, pts, x, kernel_name=kname, param=param,
                            tile_j=64, tile_i=64, interpret=True)
    want = ref.kernel_matvec_ref(pts, pts, x, kname, param)
    rel = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
    assert rel < 2e-5, rel


def test_kernel_matvec_rectangular():
    """Separate source/target sets (Nyström W_XY blocks, KRR prediction)."""
    a = jnp.asarray(RNG.normal(size=(150, 2)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(220, 2)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(220,)), jnp.float32)
    out = ops.kernel_matvec(a, b, x, kernel_name="gaussian", param=1.0,
                            zero_diagonal=False, tile_j=64, tile_i=64,
                            interpret=True)
    want = ref.kernel_matvec_ref(a, b, x, "gaussian", 1.0, zero_diagonal=False)
    rel = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
    assert rel < 2e-5, rel


# --------------------------------------------------------------- window kernels
# Separable streaming geometry: per-node patch corner (n, d) + per-dim
# weights (n, d, taps) — the fused engine's WindowGeometry layout.
def _sep_geom(n, d, taps, padded, dtype=jnp.float64):
    base = jnp.asarray(RNG.integers(0, padded - taps + 1, (n, d)), jnp.int32)
    w = jnp.asarray(RNG.normal(size=(n, d, taps)), dtype)
    return base, w


def _block(grid, d):
    """A ``(P,)*d [+ (C,)]`` grid in the kernels' lane-dense layout."""
    if grid.ndim == d:
        return nfft_window.to_grid_block(grid[..., None], d)[0]
    return nfft_window.to_grid_block(grid, d)


def _unblock(block, padded, d):
    """The kernels' grid block back to ``(P,)*d [+ (C,)]``."""
    if block.ndim == len(nfft_window.grid_block_shape(padded, d, 1)):
        return nfft_window.from_grid_block(block, padded, d)
    return nfft_window.from_grid_block(block[None], padded, d)[..., 0]


@pytest.mark.parametrize("n,d,taps,padded", [(100, 1, 9, 512), (257, 2, 9, 64),
                                             (120, 3, 5, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_window_gather_sweep(n, d, taps, padded, dtype):
    base, w = _sep_geom(n, d, taps, padded, dtype)
    g = jnp.asarray(RNG.normal(size=(padded,) * d), dtype)
    out = ops.window_gather(_block(g, d), base, w, node_tile=128,
                            interpret=True)
    want = ref.window_gather_ref(g, base, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4 if dtype == jnp.float32 else 1e-12,
                               atol=1e-4 if dtype == jnp.float32 else 1e-12)


@pytest.mark.parametrize("n,d,taps,padded", [(100, 1, 9, 512), (257, 2, 9, 64),
                                             (120, 3, 5, 40)])
def test_window_spread_sweep(n, d, taps, padded):
    base, w = _sep_geom(n, d, taps, padded)
    x = jnp.asarray(RNG.normal(size=(n,)))
    out = ops.window_spread(x, base, w, padded_size=padded, node_tile=128,
                            interpret=True)
    assert out.shape == nfft_window.grid_block_shape(padded, d, 1)[1:]
    want = ref.window_spread_ref(x, base, w, padded)
    np.testing.assert_allclose(np.asarray(_unblock(out, padded, d)),
                               np.asarray(want), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("n,d,taps,padded,c", [(100, 1, 9, 512, 3),
                                               (140, 2, 9, 64, 4),
                                               (90, 3, 5, 40, 2)])
def test_window_gather_batched_channels(n, d, taps, padded, c):
    """(C,) + ... grid blocks share one geometry stream across channels."""
    base, w = _sep_geom(n, d, taps, padded)
    g = jnp.asarray(RNG.normal(size=(padded,) * d + (c,)))
    out = ops.window_gather(_block(g, d), base, w, node_tile=128,
                            interpret=True)
    want = ref.window_gather_ref(g, base, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-12, atol=1e-12)
    for i in range(c):
        single = ops.window_gather(_block(g[..., i], d), base, w,
                                   node_tile=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out[:, i]), np.asarray(single),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("n,d,taps,padded,c", [(100, 1, 9, 512, 3),
                                               (140, 2, 9, 64, 2),
                                               (90, 3, 5, 40, 2)])
def test_window_spread_batched_channels(n, d, taps, padded, c):
    base, w = _sep_geom(n, d, taps, padded)
    x = jnp.asarray(RNG.normal(size=(n, c)))
    out = ops.window_spread(x, base, w, padded_size=padded, node_tile=128,
                            interpret=True)
    want = ref.window_spread_ref(x, base, w, padded)
    np.testing.assert_allclose(np.asarray(_unblock(out, padded, d)),
                               np.asarray(want), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("d,taps,padded", [(1, 9, 256), (2, 9, 64),
                                           (3, 5, 40)])
def test_spread_gather_adjoint(d, taps, padded):
    """<gather(g), x> == <g, spread(x)> — the NFFT adjointness at tile level."""
    n = 200
    base, w = _sep_geom(n, d, taps, padded)
    g = jnp.asarray(RNG.normal(size=(padded,) * d))
    x = jnp.asarray(RNG.normal(size=(n,)))
    lhs = float(jnp.vdot(ops.window_gather(_block(g, d), base, w,
                                           interpret=True), x))
    rhs = float(jnp.vdot(g, _unblock(
        ops.window_spread(x, base, w, padded_size=padded, interpret=True),
        padded, d)))
    assert abs(lhs - rhs) / abs(lhs) < 1e-12


# The lane-dense layout against the XLA window path, through the engine's
# window step (layout conversion, channel chunks, fold and roll included):
# d = 1..3 at C = 1 and 4, the spiral's d=3 SETUP_2 shape (P = 72, 9 taps),
# and grids whose windows cross a 128-lane tile boundary (P = 142, 262).
_LANE_DENSE_PLANS = {
    "d1_small": FastsumParams(n_bandwidth=16, m=3).nfft_plan(1),
    "d2_small": FastsumParams(n_bandwidth=16, m=3).nfft_plan(2),
    "d3_small": FastsumParams(n_bandwidth=16, m=2).nfft_plan(3),
    "spiral_setup2_d3": SETUP_2.nfft_plan(3),
    "setup3_d1_cross_lanes": SETUP_3.nfft_plan(1),
    "crescent_d2_cross_lanes": FastsumParams(n_bandwidth=128,
                                             m=3).nfft_plan(2),
}


def _engine_geometry(plan, n):
    base, w = _sep_geom(n, plan.d, plan.taps, padded_grid_size(plan))
    perm = RNG.permutation(n)
    return WindowGeometry(base=base, weights=w,
                          perm=jnp.asarray(perm, jnp.int32),
                          inv=jnp.asarray(np.argsort(perm), jnp.int32))


def _engine_parity(plan, n, c):
    geometry = _engine_geometry(plan, n)
    x = jnp.asarray(RNG.normal(size=(n, c)))
    g = jnp.asarray(RNG.normal(size=(plan.grid_size,) * plan.d + (c,)))
    for fn, arg in ((fastsum_exec.window_spread, x),
                    (fastsum_exec.window_gather, g)):
        via_xla = fn(plan, geometry, arg, backend="xla")
        via_pallas = fn(plan, geometry, arg, backend="pallas")
        assert via_pallas.shape == via_xla.shape
        np.testing.assert_allclose(np.asarray(via_pallas),
                                   np.asarray(via_xla), rtol=1e-12,
                                   atol=1e-12)


@pytest.mark.parametrize("c", [1, 4])
@pytest.mark.parametrize("plan_name", sorted(_LANE_DENSE_PLANS))
def test_lane_dense_window_step_matches_xla(plan_name, c):
    plan = _LANE_DENSE_PLANS[plan_name]
    if "cross_lanes" in plan_name:
        assert padded_grid_size(plan) > 128
    _engine_parity(plan, 150, c)


def test_lane_dense_window_step_in_channel_chunks(monkeypatch):
    """A grid of more channels than VMEM holds runs in near-equal channel
    chunks, one kernel call each, and matches the XLA path."""
    plan = _LANE_DENSE_PLANS["d3_small"]
    pad = padded_grid_size(plan)
    one = (nfft_window.vmem_bytes(nfft_window.grid_block_shape(pad, 3, 1))
           + nfft_window.vmem_bytes(nfft_window._rows_shape(
               nfft_window.DEFAULT_NODE_TILE, 1)))
    monkeypatch.setattr(nfft_window, "VMEM_GRID_BUDGET", 3 * one)
    assert nfft_window.channels_per_call(pad, 3, 7) == 3  # 3 + 3 + 1 -> 3
    assert nfft_window.channels_per_call(pad, 3, 4) == 2  # 2 + 2
    assert [cs.stop - cs.start for cs in
            fastsum_exec._channel_chunks(plan, 7)] == [3, 3, 1]
    widths = []
    real_spread = nfft_window.window_spread

    def spy(x, *a, **k):
        widths.append(x.shape[-1])
        return real_spread(x, *a, **k)

    monkeypatch.setattr(nfft_window, "window_spread", spy)
    _engine_parity(plan, 120, 7)
    assert widths == [3, 3, 1]


def test_grid_block_layout_round_trip():
    """``to_grid_block`` and ``from_grid_block`` are inverse; the block
    puts the last axis on 128-lane tiles ahead of the sublane axis."""
    for d, p in ((1, 142), (2, 262), (3, 36)):
        g = jnp.asarray(RNG.normal(size=(p,) * d + (3,)))
        block = nfft_window.to_grid_block(g, d)
        assert block.shape == nfft_window.grid_block_shape(p, d, 3)
        np.testing.assert_array_equal(
            np.asarray(nfft_window.from_grid_block(block, p, d)),
            np.asarray(g))
        if d > 1:  # lane tile 0, sublane row 1, lane 2 of channel 0
            idx = (1,) * (d - 2) + (1, 2)
            assert block[(0,) + (1,) * (d - 2) + (0, 1, 2)] == g[idx + (0,)]


# -------------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,hq,hkv,sq,sk,dh", [
    (2, 4, 2, 128, 128, 64),
    (1, 8, 1, 100, 100, 64),   # MQA, ragged seq
    (1, 2, 2, 64, 192, 32),    # cross-length
    (1, 16, 8, 96, 96, 128),   # GQA group 2
])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, dh, causal):
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk, dh)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 4, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) < 5e-2


def test_flash_attention_decode_alignment():
    """Decode shape: one query against a long KV cache, causal offset."""
    q = jnp.asarray(RNG.normal(size=(2, 4, 1, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 4, 256, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=8, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5
