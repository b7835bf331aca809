"""Pencil-decomposed distributed FFT + spectral-mode parity (multidevice).

Subprocess tests (see tests/test_distributed.py for why): 8 forced host
devices, float64 so the <= 1e-10 parity bound against the single-device
fused matvec is meaningful.

Covers the PR-4 acceptance matrix: d = 2 and d = 3, single and batched
(n, C) RHS, ghost-node padding (n % P != 0), in *both* spectral modes
("psum" and "pencil"), the two-group (row x col) pencil split, and
adjoint/roundtrip/parity identities for pencil_rfftn / pencil_irfftn.
"""

import pytest

from test_distributed import run_in_subprocess

pytestmark = pytest.mark.multidevice


def test_pencil_matvec_matches_single_device():
    """distributed_matvec_fn parity vs op.matvec, both modes, d=2/3,
    single + batched RHS, n not divisible by the shard count."""
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import SETUP_1, SETUP_2, make_fastsum, make_kernel
        from repro.data.synthetic import spiral
        from repro.dist.fastsum_dist import distributed_matvec_fn

        assert jax.config.jax_enable_x64
        rng = np.random.default_rng(0)
        n = 4099  # 4099 % 8 != 0 -> ghost-node padding in play
        mesh = jax.make_mesh((8,), ("data",))
        for d, setup in ((3, SETUP_1), (2, SETUP_2)):
            pts = (spiral(n, seed=3)[0] if d == 3
                   else rng.uniform(-3, 3, (n, 2)))
            op = make_fastsum(make_kernel("gaussian", sigma=3.5),
                              jnp.asarray(pts, jnp.float64), setup)
            for mode in ("psum", "pencil"):
                mv = distributed_matvec_fn(op, mesh, ("data",),
                                           spectral_mode=mode)
                for shape in ((n,), (n, 3)):
                    x = jnp.asarray(rng.standard_normal(shape))
                    ref = op.matvec(x)
                    err = float(jnp.max(jnp.abs(mv(x) - ref)) /
                                jnp.max(jnp.abs(ref)))
                    assert err < 1e-10, (d, mode, shape, err)
        print("pencil/psum matvec parity OK")
    """, x64=True)


def test_pencil_two_group_split():
    """Row x col pencil (the past-64-devices layout): grid axis 0 sharded
    over one mesh axis, the rfft axis over the other."""
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import SETUP_1, make_fastsum, make_kernel
        from repro.data.synthetic import spiral
        from repro.dist.fastsum_dist import distributed_matvec_fn
        from repro.dist.pencil_fft import make_pencil_spec

        n = 2053
        pts, _ = spiral(n, seed=5)
        op = make_fastsum(make_kernel("gaussian", sigma=3.5),
                          jnp.asarray(pts, jnp.float64), SETUP_1)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        spec = make_pencil_spec(mesh, ("data", "model"), op.plan.grid_size,
                                3, pencil_axes=(("data",), ("model",)))
        assert spec.row_size == 4 and spec.col_size == 2, spec
        mv = distributed_matvec_fn(op, mesh, ("data", "model"),
                                   spectral_mode="pencil",
                                   pencil_axes=(("data",), ("model",)))
        rng = np.random.default_rng(1)
        for shape in ((n,), (n, 2)):
            x = jnp.asarray(rng.standard_normal(shape))
            ref = op.matvec(x)
            err = float(jnp.max(jnp.abs(mv(x) - ref)) /
                        jnp.max(jnp.abs(ref)))
            assert err < 1e-10, (shape, err)
        print("two-group pencil OK")
    """, x64=True)


def test_pencil_rfftn_adjoint_roundtrip_parity():
    """pencil_rfftn/pencil_irfftn: parity vs jnp.fft.rfftn, exact
    roundtrip, and adjointness (symmetry of the multiplier sandwich)."""
    run_in_subprocess("""
        import functools, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.fastsum import SETUP_1
        from repro.core.fastsum_exec import fused_spectral_multiplier
        from repro.dist import pencil_fft
        from jax import shard_map

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        for d in (2, 3):
            plan = SETUP_1.nfft_plan(d)
            grid, half = plan.grid_size, plan.grid_size // 2 + 1
            spec = pencil_fft.make_pencil_spec(mesh, ("data",), grid, d)
            assert spec.row_size == 8
            # radial (even) coefficients, like every production kernel's
            # b_hat: evenness is what makes the multiplier sandwich a
            # symmetric operator (the property the adjoint check asserts)
            freqs = jnp.fft.fftfreq(plan.n_bandwidth,
                                    d=1.0 / plan.n_bandwidth)
            k2 = sum(jnp.meshgrid(*([freqs ** 2] * d), indexing="ij"))
            b_hat = jnp.exp(-k2 / 7.0).astype(complex)
            mult = fused_spectral_multiplier(plan, b_hat)
            x = jnp.asarray(rng.standard_normal((grid,) * d + (1,)))
            y = jnp.asarray(rng.standard_normal((grid,) * d + (1,)))

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P(), P(), P()),
                               out_specs=(P(), P(), P()),
                               check_vma=False)
            def run(mult_, x_, y_):
                rows = grid // spec.row_size
                r = pencil_fft.group_index(spec.row_axes, spec.row_sizes)
                sl = lambda v: jax.lax.dynamic_slice_in_dim(
                    v, r * rows, rows, axis=0)
                fwd = pencil_fft.pencil_rfftn(sl(x_), spec)
                # roundtrip on the pencil (worst error across all shards)
                rt_err = jax.lax.pmax(jnp.max(jnp.abs(
                    pencil_fft.pencil_irfftn(fwd, spec) - sl(x_))),
                    spec.row_axes)
                # parity: reassemble the (padded) half-spectrum
                gather_ax = 1
                full = jax.lax.all_gather(fwd, spec.row_axes, axis=gather_ax,
                                          tiled=True)
                if d == 2:
                    full = full[:, :half]
                par_err = jnp.max(jnp.abs(
                    full - jnp.fft.rfftn(x_, axes=tuple(range(d)))))
                # adjointness: S = irfftn . mult . rfftn is symmetric for the
                # Hermitian-symmetrized production multiplier
                slab = pencil_fft.multiplier_slab(mult_, spec)

                def s_op(v):
                    gh = pencil_fft.pencil_rfftn(sl(v), spec)
                    out = pencil_fft.pencil_irfftn(
                        gh * slab.astype(gh.dtype)[..., None], spec)
                    return jax.lax.all_gather(out, spec.row_axes, axis=0,
                                              tiled=True)

                lhs = jnp.vdot(y_, s_op(x_))
                adj_err = (jnp.abs(lhs - jnp.vdot(x_, s_op(y_)))
                           / jnp.maximum(jnp.abs(lhs), 1.0))
                scale = jnp.maximum(jnp.max(jnp.abs(full)), 1.0)
                return (rt_err[None], par_err[None] / scale, adj_err[None])

            rt, par, adj = (float(v[0]) for v in run(mult, x, y))
            assert rt < 1e-12, (d, rt)
            assert par < 1e-12, (d, par)
            assert adj < 1e-12, (d, adj)
        print("pencil fft adjoint/roundtrip/parity OK")
    """, x64=True)
