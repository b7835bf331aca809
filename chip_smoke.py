#!/usr/bin/env python3
"""Smoke run of the NFFT-Lanczos main path on one TPU chip.

Drives the normal entry points at the paper's scale, in float32, in this
one process (a chip belongs to one process, so nothing here starts a
child that needs it):

* fig5    image segmentation (paper Fig. 5): ``synthetic_image(533, 800)``,
          426,400 RGB nodes, d=3, Gaussian sigma=90, N=16 m=2 p=2
          eps_B=1/8, through ``make_normalized_adjacency`` and
          ``spectral_clustering`` (k=4);
* spiral  paper Fig. 3 data, d=3, SETUP_2, n=100,000: ``eigsh`` k=10,
          single-vector and ``block_size=4``.

The crescent kernel-SSL CG solve (d=2) is the benchmark's
``crescent.ssl_cg`` cell (``bench/``).

Each phase is checked against the float32 direct product
(``direct_matvec_tiled``, O(n^2) work in row blocks; no dense matrix and
no dense ``eigh``), and prints the device kind, the window backend that
ran at its d (read from the compiled program: a Pallas kernel shows up as a
``tpu_custom_call``), compile and steady seconds, and each error next to
its limit.  These are smoke numbers, not benchmark numbers.

``--four-chips`` runs only the distributed matvec on a 4-device mesh
(``distributed_matvec_fn`` in both spectral modes against the one-device
``op.matvec``, plus one ``eigsh`` on it).

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed phase exits non-zero without it; so does a host without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)  # float32: all a TPU computes in

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    SETUP_1, SETUP_2, FastsumParams, direct_matvec_tiled, eigsh, make_kernel,
    make_normalized_adjacency,
)
from repro.core.fastsum_exec import resolve_backend  # noqa: E402
from repro.data.synthetic import spiral, synthetic_image  # noqa: E402
from repro.dist.fastsum_dist import (  # noqa: E402
    distributed_matvec_fn, make_sharded_matvec, resolve_pencil_spec,
)
from repro.graph.spectral import (  # noqa: E402
    clustering_agreement, spectral_clustering,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Paper Fig. 5 (benchmarks/fig5_segmentation.py): pixels in RGB space.
FIG5_SIGMA = 90.0
FIG5_PARAMS = FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=1.0 / 8.0)
# Paper Fig. 3 spiral; the data generator is calibrated to sigma = 3.5.
SPIRAL_SIGMA = 3.5

# The direct reference's row block: (tile, n) kernel values per step.
DIRECT_TILE = 512

# Limits.  Each starts from the setup's NFFT error, measured against the
# float64 direct product on the same generators at n = 5,000-5,400 on the
# CPU (the error depends on how the data scales into the NFFT ball and on
# the setup, not on n), and allows for float32 rounding: ~1e-6 relative
# per matvec (FFT and window sums in float32; the direct product sums n
# terms at float32 precision).  Eigenpairs are held to the excess of the
# true residual ||A v - lam v|| (A through the direct product) over the
# Lanczos residual bound: what the NFFT operator and the chip's arithmetic
# add, separate from how far the Krylov subspace has converged.
LIMITS = {
    # N=16 m=2 p=2 on RGB data at sigma=90: the truncated Gaussian leaves
    # 6.4e-4 (random x), 3.2e-4 (degrees), 8.8e-4 (eigen-residual) in
    # float64 — the paper accepts this for segmentation.  ~6x margin.
    "fig5_matvec_rel": 5e-3,
    "fig5_eig_excess": 5e-3,
    # SETUP_2 leaves 5e-8 in float64; float32 rounding (1.0e-6 on the CPU
    # rehearsal) dominates.  ~100x margin for the chip's exp/FFT rounding.
    "spiral_matvec_rel": 1e-4,
    "spiral_eig_excess": 1e-4,
    # psum/pencil against the one-device matvec: same arithmetic in a
    # different summation order, float32.
    "dist_parity_rel": 1e-5,
    "dist_eig_abs": 1e-4,
}

_KERNEL_RE = re.compile(
    r"%([A-Za-z_]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"")


class PhaseFailed(RuntimeError):
    pass


def log(phase: str, **kv) -> None:
    parts = []
    for k, v in kv.items():
        parts.append(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}")
    print(f"[{phase}] " + " ".join(parts), flush=True)


def check(phase: str, name: str, value: float, limit: float) -> None:
    ok = bool(np.isfinite(value)) and value <= limit
    log(phase, check=name, value=float(value), limit=limit,
        result="ok" if ok else "FAIL")
    if not ok:
        raise PhaseFailed(f"{phase}: {name} = {value:.3e} > {limit:.1e}")


def compile_and_run(phase: str, fn, *args):
    """AOT-compile ``fn`` and run it once; log compile and steady seconds
    (the run excludes compilation) and the kernels in the compiled
    program."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    kernels = sorted(set(_KERNEL_RE.findall(compiled.as_text())))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    steady_s = time.perf_counter() - t0
    log(phase, compile_s=compile_s, steady_s=steady_s,
        tpu_custom_calls=",".join(kernels) or "none")
    return out, kernels


def check_window_backend(phase: str, plan, channels: int, kernels) -> str:
    """The backend read from the compiled program must be the one the
    selection rule predicts for this grid."""
    ran = "pallas" if {"window_spread", "window_gather"} & set(kernels) \
        else "xla"
    expected = resolve_backend("auto", plan, channels, jnp.float32)
    log(phase, d=plan.d, channels=channels, window_backend=ran,
        rule=expected)
    if ran != expected:
        raise PhaseFailed(f"{phase}: window backend {ran}, rule says "
                          f"{expected}")
    return ran


def rel_err(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


class DirectReference:
    """A = D^{-1/2} W D^{-1/2} applied through the float32 direct product."""

    def __init__(self, kernel, points):
        self.kernel, self.points = kernel, points
        t0 = time.perf_counter()
        self.deg = self.w(jnp.ones((points.shape[0],), points.dtype))
        jax.block_until_ready(self.deg)
        self.seconds = time.perf_counter() - t0
        self.s = 1.0 / jnp.sqrt(self.deg)

    def w(self, x):
        return direct_matvec_tiled(self.kernel, self.points, x,
                                   tile=DIRECT_TILE)

    def a(self, v):
        s = self.s if v.ndim == 1 else self.s[:, None]
        return s * self.w(s * v)


def eig_check(phase: str, ref: DirectReference, res, limit: str) -> None:
    """||A v_j - lam_j v_j|| / ||v_j|| (A: the direct product) against the
    Lanczos bound for the same pair; the excess is what is checked."""
    vecs = res.eigenvectors
    r = ref.a(vecs) - vecs * res.eigenvalues[None, :]
    true = jnp.linalg.norm(r, axis=0) / jnp.linalg.norm(vecs, axis=0)
    log(phase, eig_residual_max=float(jnp.max(true)),
        lanczos_bound_max=float(jnp.max(res.residual_bounds)))
    check(phase, "eig_residual_excess_max",
          float(jnp.max(true - res.residual_bounds)), LIMITS[limit])


def matvec_check(phase: str, op, ref: DirectReference, limit: str,
                 seed: int) -> None:
    x = jax.random.normal(jax.random.PRNGKey(seed), (op.n,), jnp.float32)
    check(phase, "matvec_rel_err", rel_err(op.fastsum.matvec(x), ref.w(x)),
          LIMITS[limit])
    check(phase, "degree_rel_err", rel_err(op.degrees, ref.deg),
          LIMITS[limit])


def phase_fig5(height: int = 533, width: int = 800) -> None:
    phase = "fig5"
    img, truth = synthetic_image(height, width)
    pixels = jnp.asarray(img.reshape(-1, 3), jnp.float32)
    kernel = make_kernel("gaussian", sigma=FIG5_SIGMA)
    log(phase, n=pixels.shape[0], d=3, sigma=FIG5_SIGMA, k=4,
        params="N=16,m=2,p=2,eps_B=1/8")

    def run(pixels, key):
        op = make_normalized_adjacency(kernel, pixels, FIG5_PARAMS)
        return op, spectral_clustering(op, 4, key=key)

    (op, res), kernels = compile_and_run(phase, run, pixels,
                                         jax.random.PRNGKey(0))
    check_window_backend(phase, op.fastsum.plan, 1, kernels)
    agree = clustering_agreement(truth.reshape(-1),
                                 np.asarray(res.assignments), 4)
    log(phase, eigenvalues=np.array2string(np.asarray(res.eigenvalues),
                                           precision=6),
        label_agreement=agree)
    ref = DirectReference(kernel, pixels)
    log(phase, direct_reference_s=ref.seconds)
    matvec_check(phase, op, ref, "fig5_matvec_rel", seed=1)
    eig_check(phase, ref, res, "fig5_eig_excess")


def phase_spiral(n: int = 100_000) -> None:
    phase = "spiral"
    points, _ = spiral(n, seed=1)
    pts = jnp.asarray(points, jnp.float32)
    kernel = make_kernel("gaussian", sigma=SPIRAL_SIGMA)
    log(phase, n=n, d=3, sigma=SPIRAL_SIGMA, setup="SETUP_2", k=10)
    ref = DirectReference(kernel, pts)
    log(phase, direct_reference_s=ref.seconds)
    for block in (1, 4):
        sub = f"{phase}/block{block}"

        def run(pts, key, block=block):
            op = make_normalized_adjacency(kernel, pts, SETUP_2)
            return op, eigsh(op.matvec, n, 10, key=key, block_size=block,
                             dtype=jnp.float32)

        (op, res), kernels = compile_and_run(sub, run, pts,
                                             jax.random.PRNGKey(2))
        check_window_backend(sub, op.fastsum.plan, block, kernels)
        log(sub, num_matvecs=res.num_matvecs,
            eigenvalues=np.array2string(np.asarray(res.eigenvalues),
                                        precision=6))
        if block == 1:
            matvec_check(sub, op, ref, "spiral_matvec_rel", seed=3)
        eig_check(sub, ref, res, "spiral_eig_excess")


def phase_four_chips(n: int = 100_000) -> None:
    """distributed_matvec_fn, psum and pencil, against the one-device
    op.matvec on a 4-device mesh; then one eigsh on the sharded matvec."""
    phase = "four_chips"
    if jax.device_count() < 4 or n % 4:
        raise PhaseFailed(f"{phase}: needs 4 devices and 4 | n, found "
                          f"{jax.device_count()} devices, n = {n}")
    mesh = jax.make_mesh((4,), ("data",))
    devices = set(mesh.devices.flat)
    points, _ = spiral(n, seed=1)
    pts = jnp.asarray(points, jnp.float32)
    kernel = make_kernel("gaussian", sigma=SPIRAL_SIGMA)
    x = jax.random.normal(jax.random.PRNGKey(6), (n,), jnp.float32)
    # both setups run the Pallas window kernels inside shard_map
    ops = {}
    for setup_name, setup in (("SETUP_2", SETUP_2), ("SETUP_1", SETUP_1)):
        op = ops[setup_name] = make_normalized_adjacency(kernel, pts, setup)
        ref = op.fastsum.matvec(x)
        window = resolve_backend("auto", op.fastsum.plan, 1, jnp.float32)
        for mode in ("psum", "pencil"):
            sub = f"{phase}/{setup_name}/{mode}"
            effective = mode
            if mode == "pencil" and resolve_pencil_spec(
                    op.fastsum.plan, mesh, ("data",)) is None:
                effective = "psum"
            log(sub, n=n, d=3, window_backend=window,
                spectral_mode_effective=effective)
            if effective != mode:
                raise PhaseFailed(f"{sub}: pencil degraded to psum")
            mv = distributed_matvec_fn(op.fastsum, mesh, ("data",),
                                       spectral_mode=mode)
            t0 = time.perf_counter()
            out = jax.block_until_ready(mv(x))
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = jax.block_until_ready(mv(x))
            log(sub, first_s=first_s, steady_s=time.perf_counter() - t0)
            # the sharded body itself: one nonzero block of n/4 rows per chip
            body = make_sharded_matvec(op.fastsum.plan, mesh, ("data",),
                                       spectral_mode=mode)
            win = op.fastsum.src_window
            y = body(op.fastsum.multiplier_half, win.base, win.weights,
                     x[win.perm][:, None])
            used = {sh.device for sh in y.addressable_shards
                    if sh.data.shape[0] == n // 4
                    and bool(jnp.any(sh.data != 0))}
            log(sub, devices_with_work=len(used))
            if used != devices:
                raise PhaseFailed(f"{sub}: work on {len(used)} of 4 devices")
            check(sub, "parity_rel_err", rel_err(out, ref),
                  LIMITS["dist_parity_rel"])
    # one eigsh on the sharded matvec (SETUP_1, pencil) vs one device
    op = ops["SETUP_1"]
    mv_w = distributed_matvec_fn(op.fastsum, mesh, ("data",),
                                 spectral_mode="pencil")
    s = op.inv_sqrt_deg
    key = jax.random.PRNGKey(2)
    dist = eigsh(lambda v: s * mv_w(s * v), n, 10, key=key,
                 dtype=jnp.float32)
    single = eigsh(op.matvec, n, 10, key=key, dtype=jnp.float32)
    log(f"{phase}/eigsh", eigenvalues=np.array2string(
        np.asarray(dist.eigenvalues), precision=6))
    check(f"{phase}/eigsh", "eig_abs_diff_vs_one_device",
          float(jnp.max(jnp.abs(dist.eigenvalues - single.eigenvalues))),
          LIMITS["dist_eig_abs"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device distributed matvec phase")
    args = ap.parse_args()

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform '{platform}' "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    log("device", platform=platform, kind=kind, count=len(devices),
        precision="float32", compile_cache=cache)

    phases = ([phase_four_chips] if args.four_chips
              else [phase_fig5, phase_spiral])
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception as e:  # noqa: BLE001 — report every phase
            failed.append(phase.__name__)
            log(phase.__name__, result="FAIL",
                error=f"{type(e).__name__}: {e}".replace("\n", " ")[:2000])
        log(phase.__name__, device_kind=kind,
            wall_s=time.perf_counter() - t0)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
