"""End-to-end dry-run machinery test on a small forced mesh (subprocess).

Exercises launch/steps.py + launch/dryrun.py + the loop-aware analyzer on a
reduced-config train cell with 16 host devices — the same code path the
512-device production dry-run uses, cheap enough for CI.
"""

import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_reduced_cell_lower_compile_roofline():
    code = """
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.launch import hlo_analysis as H
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import lower_cell
        from repro.training.train_loop import TrainConfig

        cfg = reduced_config(get_config("granite-3-2b"), seq_len=64,
                             global_batch=8)
        # give the smoke config its real shape list entry
        shape = cfg.shapes[0]
        mesh = make_mesh((4, 4), ("data", "model"))
        tc = TrainConfig(num_microbatches=2)
        lowered, kind = lower_cell(cfg, shape, mesh, tc=tc)
        assert kind == "train"
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        assert ma is not None
        stats = H.analyze(compiled.as_text(), pod_boundary=8)
        # scan over 4 layers x 2 microbatches -> trip counts visible
        assert any(t == 4 for t in stats.while_trip_counts), \\
            stats.while_trip_counts
        assert stats.flops > 0
        assert stats.collective_bytes > 0  # TP/FSDP collectives exist
        print("dryrun cell OK", stats.while_trip_counts,
              f"{stats.flops:.3e}")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"


def test_graph_cell_pencil_payload_scales_inverse_p():
    """The dry-run pencil cells' per-device collective payload scales ~1/P
    while the psum cells' stays flat (and pencil wins at the larger mesh).

    Lowers the shipped fused matvec body (not the retired seed
    `_spectral_matvec_local`) on 8- and 32-chip meshes via
    `run_graph_cell(..., spectral_mode=...)` — the same code path as the
    512-chip `graph-fastsum-pencil-*` production cells.
    """
    code = """
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.launch.dryrun import run_graph_cell

        devs = np.array(jax.devices())
        mesh8 = Mesh(devs[:8].reshape(2, 4), ("data", "model"))
        mesh32 = Mesh(devs[:32].reshape(8, 4), ("data", "model"))

        def cell(mesh, mode):
            rec = run_graph_cell(4096, 3, False, setup_name="setup2",
                                 spectral_mode=mode, mesh=mesh)
            assert rec["status"] == "ok", rec.get("error")
            return rec

        psum8, psum32 = cell(mesh8, "psum"), cell(mesh32, "psum")
        pen8, pen32 = cell(mesh8, "pencil"), cell(mesh32, "pencil")
        assert pen32["spectral_mode_effective"] == "pencil", pen32
        pay = lambda r: r["hlo_stats"]["collective_payload_bytes"]
        kinds = lambda r: r["hlo_stats"]["collective_by_kind"]

        # the pencil path is reduce-scatter/all-to-all/all-gather, no psum
        assert "all-reduce" in kinds(psum32), kinds(psum32)
        assert "all-to-all" in kinds(pen32), kinds(pen32)
        assert "reduce-scatter" in kinds(pen32), kinds(pen32)
        assert "all-reduce" not in kinds(pen32), kinds(pen32)

        # psum payload is flat in P; pencil payload drops ~1/P (4x here)
        assert abs(pay(psum8) / pay(psum32) - 1.0) < 0.05, \\
            (pay(psum8), pay(psum32))
        ratio = pay(pen8) / pay(pen32)
        assert 3.0 < ratio < 5.0, (pay(pen8), pay(pen32), ratio)
        # past the crossover the sharded spectrum beats the flat psum
        assert pay(pen32) < 0.6 * pay(psum32), (pay(pen32), pay(psum32))
        print("pencil payload OK",
              pay(psum8), pay(psum32), pay(pen8), pay(pen32))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"


def test_graph_cell_bank_payload_scales_with_s():
    """The bank dry-run cells lower the shipped bank body: the one
    cross-shard collective carries the S stacked channel lanes, so its
    per-device payload is ~S x the matching S=1 cell's — while the cell
    still lowers (and the S=1/S=8 comparison confirms) a single spread +
    forward-FFT stage, not S of them."""
    code = """
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.launch.dryrun import run_graph_cell

        devs = np.array(jax.devices())
        mesh = Mesh(devs[:8].reshape(2, 4), ("data", "model"))
        for mode in ("psum", "pencil"):
            r1 = run_graph_cell(4096, 3, False, setup_name="setup1",
                                spectral_mode=mode, mesh=mesh, bank_size=1)
            rb = run_graph_cell(4096, 3, False, setup_name="setup1",
                                spectral_mode=mode, mesh=mesh, bank_size=8)
            assert r1["status"] == "ok", r1.get("error")
            assert rb["status"] == "ok", rb.get("error")
            assert rb["bank"] == 8 and "bank8" in rb["arch"], rb["arch"]
            p1 = r1["hlo_stats"]["collective_payload_bytes"]
            pb = rb["hlo_stats"]["collective_payload_bytes"]
            ratio = pb / p1
            assert 7.0 < ratio < 9.0, (mode, p1, pb, ratio)
            print(mode, "bank payload OK", p1, pb)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"


def test_decode_cell_serve_sharding():
    code = """
        import dataclasses, jax
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import lower_cell, _serve_replicated
        from repro.training.train_loop import TrainConfig

        cfg = reduced_config(get_config("granite-3-2b"), seq_len=64,
                             global_batch=8)
        mesh = make_mesh((4, 4), ("data", "model"))
        assert _serve_replicated(cfg, mesh)  # tiny model: TP-resident
        decode = [s for s in cfg.shapes if s.kind == "decode"
                  and not s.skip_reason][0]
        lowered, kind = lower_cell(cfg, decode, mesh,
                                   tc=TrainConfig(num_microbatches=1))
        assert kind == "decode"
        lowered.compile()
        print("decode cell OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"


def test_graph_serve_cell_lowers_tick_body():
    """The serving-tier dry-run cell lowers the steady-state tick body
    (packed target geometry + ragged column gather) on a forced mesh: it
    compiles, rows pad to shard evenly, and — the serving property — the
    only cross-shard traffic is the O(rows) Morton sort of the packed
    query points themselves (the tiny per-tick working set), never a
    spectrum- or node-count-sized reduction like the training matvec's
    psum: payload stays bounded by a small multiple of the pack size, and
    no all-reduce appears at either pack size."""
    code = """
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.launch.dryrun import run_graph_serve_cell

        devs = np.array(jax.devices())
        mesh = Mesh(devs[:8].reshape(2, 4), ("data", "model"))
        def cell(chunk):
            rec = run_graph_serve_cell(8, chunk, 3, False,
                                       setup_name="setup1", mesh=mesh)
            assert rec["status"] == "ok", rec.get("error")
            return rec
        rec = cell(100)
        assert rec["kind"] == "graph_serve_tick"
        assert rec["rows"] % 8 == 0 and rec["rows"] >= 800, rec["rows"]
        assert rec["channels"] == 8
        rec2 = cell(200)
        for r in (rec, rec2):
            kinds = r["hlo_stats"]["collective_by_kind"]
            assert "all-reduce" not in kinds, kinds
            pay = r["hlo_stats"]["collective_payload_bytes"]
            # O(rows) working set, never spectrum/node-sized: the
            # distributed sort moves a few hundred bytes/row, orders of
            # magnitude below the training matvec's half-spectrum psum
            assert 0 < pay < 512 * r["rows"], (pay, r["rows"], kinds)
        print("serve cell OK", rec["rows"], rec2["rows"])
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"
