"""Multi-device sharding tests.

These MUST run in a subprocess: the host-platform device count is locked at
first jax init, and the main pytest process must keep seeing 1 device (the
smoke tests depend on it).  Each test spawns ``python -c`` with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_in_subprocess(code: str, devices: int = 8, timeout: int = 600,
                      x64: bool = False) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = REPO_SRC
    if x64:  # pencil/fused parity tests assert <= 1e-10: needs float64
        env["JAX_ENABLE_X64"] = "1"
    else:
        env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_distributed_fastsum_matches_single_device():
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import SETUP_2, make_fastsum, make_kernel
        from repro.data.synthetic import spiral
        from repro.dist.fastsum_dist import distributed_matvec_fn

        assert jax.device_count() == 8, jax.device_count()
        n = 4096
        points, _ = spiral(n, seed=3)
        pts = jnp.asarray(points, jnp.float32)
        kernel = make_kernel("gaussian", sigma=3.5)
        op = make_fastsum(kernel, pts, SETUP_2)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(n),
                        jnp.float32)
        ref = op.matvec(x)

        mesh = jax.make_mesh((8,), ("data",))
        mv = distributed_matvec_fn(op, mesh, ("data",))
        out = mv(x)
        err = float(jnp.max(jnp.abs(out - ref)) /
                    jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))
        assert err < 2e-5, err
        print("fastsum dist OK", err)
    """)


def test_distributed_matvec_on_the_lane_dense_kernels():
    """The distributed matvec's local window step on the Pallas kernels
    (interpret mode on the CPU), in both spectral modes, at d = 2 and 3,
    against the one-device matvec on the XLA window path."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import FastsumParams, make_fastsum, make_kernel
        from repro.dist.fastsum_dist import distributed_matvec_fn

        rng = np.random.default_rng(0)
        mesh = jax.make_mesh((4,), ("data",))
        for d in (2, 3):
            pts = jnp.asarray(rng.normal(size=(256, d)), jnp.float32)
            op = make_fastsum(make_kernel("gaussian", sigma=2.0), pts,
                              FastsumParams(n_bandwidth=16, m=2))
            x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
            ref = op.matvec(x, backend="xla")
            for mode in ("psum", "pencil"):
                mv = distributed_matvec_fn(op, mesh, ("data",),
                                           backend="pallas",
                                           spectral_mode=mode)
                err = float(jnp.linalg.norm(mv(x) - ref)
                            / jnp.linalg.norm(ref))
                assert err < 1e-5, (d, mode, err)
                print("OK", d, mode, err)
    """, devices=4)
    assert out.count("OK") == 4


def test_distributed_matvec_gradient_matches_single_device():
    """Reverse mode through the sharded matvec, in both spectral modes: the
    gradient with respect to x equals the one-device operator's."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import FastsumParams, make_fastsum, make_kernel
        from repro.dist.fastsum_dist import distributed_matvec_fn

        rng = np.random.default_rng(0)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
        pts = jnp.asarray(rng.normal(size=(203, 3)))
        op = make_fastsum(make_kernel("gaussian", sigma=3.5), pts,
                          FastsumParams(n_bandwidth=16, m=3))
        x = jnp.asarray(rng.normal(size=(203,)))
        w = jnp.asarray(rng.normal(size=(203,)))
        ref = jax.grad(lambda v: jnp.vdot(w, op.matvec(v)))(x)
        for mode in ("psum", "pencil"):
            mv = distributed_matvec_fn(op, mesh, ("a", "b"),
                                       spectral_mode=mode)
            g = jax.grad(lambda v: jnp.vdot(w, mv(v)))(x)
            err = float(jnp.max(jnp.abs(g - ref)) / jnp.max(jnp.abs(ref)))
            assert err < 1e-10, (mode, err)
            print("grad", mode, err)
    """, devices=4, x64=True)
    assert "grad pencil" in out


def test_distributed_bank_matvec_matches_single_device():
    """Operator-bank routing through the sharded matvec (PR 5): both
    spectral modes, broadcast and lockstep flavors, ghost-padded n, parity
    <=1e-10 vs the single-device bank in float64."""
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import SETUP_2, make_fastsum_bank, make_kernel
        from repro.data.synthetic import spiral
        from repro.dist.fastsum_dist import distributed_matvec_bank_fn

        assert jax.device_count() == 8
        n = 1000  # not divisible by 8 -> ghost-node padding
        points, _ = spiral(n, seed=3)
        pts = jnp.asarray(points)
        kernels = [make_kernel("gaussian", sigma=s) for s in (2.0, 3.5, 5.0)]
        bank = make_fastsum_bank(kernels, pts, SETUP_2)
        rng = np.random.default_rng(0)
        cases = [jnp.asarray(rng.standard_normal(n)),
                 jnp.asarray(rng.standard_normal((n, 2))),
                 jnp.asarray(rng.standard_normal((3, n, 2)))]
        mesh = jax.make_mesh((8,), ("data",))
        for mode in ("psum", "pencil"):
            mv = distributed_matvec_bank_fn(bank, mesh, ("data",),
                                            spectral_mode=mode)
            for x in cases:
                ref = bank.matvec(x)
                out = mv(x)
                err = float(jnp.max(jnp.abs(out - ref))
                            / jnp.max(jnp.abs(ref)))
                assert err < 1e-10, (mode, x.shape, err)
        print("dist bank OK")
    """, x64=True)


def test_distributed_lanczos_eigs():
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (SETUP_2, dense_normalized_adjacency, eigsh,
                                make_kernel, make_normalized_adjacency,
                                make_fastsum)
        from repro.data.synthetic import spiral
        from repro.dist.fastsum_dist import distributed_matvec_fn

        n = 2048
        points, _ = spiral(n, seed=4)
        pts = jnp.asarray(points, jnp.float32)
        kernel = make_kernel("gaussian", sigma=3.5)
        op = make_normalized_adjacency(kernel, pts, SETUP_2)
        mesh = jax.make_mesh((8,), ("data",))
        mv_w = distributed_matvec_fn(op.fastsum, mesh, ("data",))
        inv_sqrt = op.inv_sqrt_deg
        mv_a = lambda x: inv_sqrt * mv_w(inv_sqrt * x)
        res = eigsh(mv_a, n, 5, key=jax.random.PRNGKey(0), dtype=pts.dtype)

        a = dense_normalized_adjacency(kernel, pts)
        lam = jnp.linalg.eigvalsh(a)[::-1][:5]
        err = float(jnp.max(jnp.abs(res.eigenvalues - lam)))
        assert err < 5e-4, err
        print("dist lanczos OK", err)
    """)


def test_sharded_train_step_matches_single_device():
    run_in_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config, reduced_config
        from repro.data.pipeline import batch_for_step
        from repro.dist import sharding as shr
        from repro.launch.steps import shardings_for
        from repro.launch.mesh import make_mesh
        from repro.models.common import set_mesh
        from repro.training.train_loop import (TrainConfig, init_train_state,
                                               make_train_step)

        cfg = reduced_config(get_config("granite-3-2b"), global_batch=8)
        tc = TrainConfig(num_microbatches=2)
        state = init_train_state(jax.random.PRNGKey(0), cfg, tc)
        batch = jax.tree.map(jnp.asarray,
                             batch_for_step(cfg, cfg.shapes[0], 0))
        # single-device reference
        _, ref = jax.jit(make_train_step(cfg, tc))(state, batch)
        ref_loss = float(ref["loss"])

        mesh = make_mesh((4, 2), ("data", "model"))
        in_sh = shardings_for("train", (state, batch), mesh)
        with mesh, set_mesh(mesh):
            step = jax.jit(make_train_step(cfg, tc), in_shardings=in_sh)
            new_state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        assert abs(loss - ref_loss) < 1e-4, (loss, ref_loss)
        print("sharded train OK", loss, ref_loss)
    """)


def test_compress_psum_shard_map():
    run_in_subprocess("""
        import functools, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.dist.compression import compress_psum

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((8, 1000)), jnp.float32)
        resid = jnp.zeros_like(g)

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           check_vma=False)
        def reduce_grads(gs, rs):
            mean, new_r = compress_psum(gs[0], "data", rs[0])
            return mean[None], new_r[None]

        mean, new_resid = reduce_grads(g, resid)
        ref = jnp.mean(g, axis=0)
        # every worker's copy approximates the exact mean
        err = float(jnp.max(jnp.abs(mean - ref[None, :])))
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        assert err <= scale * 1.01, (err, scale)
        print("compress psum OK", err)
    """)


def test_elastic_restore_across_meshes():
    """Checkpoint saved under one sharding restores + trains on another —
    the elastic-rescale contract of the checkpoint format."""
    run_in_subprocess("""
        import os, tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.data.pipeline import batch_for_step
        from repro.dist import sharding as shr
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import shardings_for
        from repro.models.common import set_mesh
        from repro.training import checkpoint as ckpt
        from repro.training.train_loop import (TrainConfig, init_train_state,
                                               make_train_step)

        cfg = reduced_config(get_config("granite-3-2b"), global_batch=8)
        tc = TrainConfig(num_microbatches=1)
        state = init_train_state(jax.random.PRNGKey(0), cfg, tc)
        batch = jax.tree.map(jnp.asarray,
                             batch_for_step(cfg, cfg.shapes[0], 0))

        tmp = tempfile.mkdtemp()
        # phase 1: train 2 steps on an (8,1) data-parallel mesh, checkpoint
        mesh1 = make_mesh((8, 1), ("data", "model"))
        sh1 = shardings_for("train", (state, batch), mesh1)
        with mesh1, set_mesh(mesh1):
            step1 = jax.jit(make_train_step(cfg, tc), in_shardings=sh1)
            state = jax.device_put(state, sh1[0])
            for s in range(2):
                state, m = step1(state, jax.tree.map(
                    jnp.asarray, batch_for_step(cfg, cfg.shapes[0], s)))
        ckpt.save_checkpoint(tmp, 2, state)
        loss_ref = None

        # phase 2: restore onto a (2,4) mesh (different DP/TP split), train
        mesh2 = make_mesh((2, 4), ("data", "model"))
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        sh2 = shardings_for("train", (abstract, batch), mesh2)
        restored = ckpt.restore_checkpoint(tmp, 2, abstract,
                                           shardings=sh2[0])
        with mesh2, set_mesh(mesh2):
            step2 = jax.jit(make_train_step(cfg, tc), in_shardings=sh2)
            restored, m2 = step2(restored, jax.tree.map(
                jnp.asarray, batch_for_step(cfg, cfg.shapes[0], 2)))
        # reference: continue on mesh1 without the restore round-trip
        with mesh1, set_mesh(mesh1):
            state, m1 = step1(state, jax.tree.map(
                jnp.asarray, batch_for_step(cfg, cfg.shapes[0], 2)))
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) < 1e-4, (l1, l2)
        print("elastic restore OK", l1, l2)
    """)


def test_production_mesh_shapes():
    run_in_subprocess("""
        from repro.launch.mesh import make_production_mesh, mesh_chip_count
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        assert mesh_chip_count(m2) == 512
        print("mesh OK")
    """, devices=512)
