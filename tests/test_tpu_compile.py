"""Compile the NFFT window kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached: it refuses what interpret mode cannot see — kernels
that need more VMEM than the chip has, unaligned slices, integer widths
Mosaic cannot lower.  Each case compiles one kernel at a main-path shape in
float32 under the suite's own x64 setting (``conftest.py`` enables it).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, so the
fixture runs in the one test worker given this file and skips where the
topology cannot be described.
"""

import base64
import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.fastsum import SETUP_2, SETUP_3, FastsumParams
from repro.core.fastsum_exec import fused_pipeline, resolve_backend
from repro.core.nfft import WindowGeometry, padded_grid_size
from repro.kernels import nfft_window

FIG5 = FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=1.0 / 8.0)
CRESCENT = FastsumParams(n_bandwidth=128, m=3, eps_b=0.0)

# (name, nodes, plan): Fig. 5 segmentation (426,400 RGB pixels; its kernel
# SSL cell runs C = 4), the crescent kernel SSL cell (d = 2, the padded
# 262^2 grid), the Fig. 3 spiral at SETUP_2 (d = 3), and the Fig. 3 / SSL
# accuracy setups at d = 2 and d = 1.
SHAPES = {
    "fig5_d3": (426_400, FIG5.nfft_plan(3)),
    "crescent_ssl_d2": (100_000, CRESCENT.nfft_plan(2)),
    "spiral_setup2_d3": (100_000, SETUP_2.nfft_plan(3)),
    "setup2_d2": (100_000, SETUP_2.nfft_plan(2)),
    "setup3_d1": (100_000, SETUP_3.nfft_plan(1)),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(kind: str, n: int, plan, channels: int, sharding):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    d, taps, pad = plan.d, plan.taps, padded_grid_size(plan)
    base = spec((n, d), jnp.int32)
    weights = spec((n, d, taps), jnp.float32)
    if kind == "spread":
        fn = jax.jit(lambda x, b, w: nfft_window.window_spread(
            x, b, w, padded_size=pad))
        args = (spec((n, channels), jnp.float32), base, weights)
    else:
        fn = jax.jit(lambda g, b, w: nfft_window.window_gather(g, b, w))
        args = (spec(nfft_window.grid_block_shape(pad, d, channels),
                     jnp.float32), base, weights)
    return fn.lower(*args).compile()


@pytest.mark.parametrize("channels", [1, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind", ["spread", "gather"])
def test_window_kernel_compiles_for_v5e(one_chip, kind, shape, channels):
    n, plan = SHAPES[shape]
    assert nfft_window.grid_fits_vmem(padded_grid_size(plan), plan.d,
                                      channels)
    compiled = _compile(kind, n, plan, channels, one_chip)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_backend_rule_picks_xla_where_the_grid_cannot_stay_resident(
        one_chip, monkeypatch):
    """A d=3 grid of which not even one channel fits VMEM (N = 256: a
    516^3 padded grid, 687 MB per channel lane-dense) takes the XLA path,
    and the compiler agrees that the kernel cannot be built there.  A grid
    whose channels do not all fit runs in chunks that do: SETUP_3 d=3
    (142^3, 20.9 MB of grid per channel) holds 3 channels per call, and the
    compiler refuses its whole 8-channel block (167 MB).  d=3 SETUP_2, on
    the XLA path while its grid padded the channels to 128 lanes, now
    takes the kernels."""
    huge = FastsumParams(n_bandwidth=256, m=2).nfft_plan(3)
    assert not nfft_window.grid_fits_vmem(padded_grid_size(huge), 3, 1)
    with pytest.raises(Exception, match="(?i)vmem|exceed memory"):
        _compile("gather", 100_000, huge, 1, one_chip)
    setup3 = SETUP_3.nfft_plan(3)
    pad3 = padded_grid_size(setup3)
    assert nfft_window.grid_fits_vmem(pad3, 3, 3)
    assert not nfft_window.grid_fits_vmem(pad3, 3, 4)
    assert nfft_window.channels_per_call(pad3, 3, 8) == 3
    with pytest.raises(Exception, match="(?i)vmem|exceed memory"):
        _compile("gather", 100_000, setup3, 8, one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto", huge, 1, jnp.float32) == "xla"
    assert resolve_backend("auto", setup3, 8, jnp.float32) == "pallas"
    for channels in (1, 4):
        assert resolve_backend("auto", SETUP_2.nfft_plan(3), channels,
                               jnp.float32) == "pallas"
    for n, fits in SHAPES.values():
        assert resolve_backend("auto", fits, 4, jnp.float32) == "pallas"
    # Mosaic has no 64-bit floats: float64 data takes the XLA path on TPU
    assert resolve_backend("auto", FIG5.nfft_plan(3), 1,
                           jnp.float64) == "xla"


def _channels_on_lanes_fits(padded_size: int, d: int, channels: int) -> bool:
    """The rule of the channels-on-lanes layout the kernels held before the
    lane-dense one: a ``(P,)*d + (C,)`` block, channels padded to 128
    lanes, within the same VMEM budget."""
    return (nfft_window.vmem_bytes((padded_size,) * d + (channels,))
            <= nfft_window.VMEM_GRID_BUDGET)


def test_every_grid_the_kernels_held_still_takes_them(monkeypatch):
    """No grid that took the Pallas kernels under the channels-on-lanes
    layout goes to XLA under the lane-dense one: every (d, P, C) of the
    kernel-compile plans, C up to 256, where the old block fitted, still
    resolves to "pallas", in channel chunks whose blocks fit."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    held = 0
    for name in ("fig5_d3", "setup2_d2", "setup3_d1"):
        plan = SHAPES[name][1]
        pad = padded_grid_size(plan)
        for channels in range(1, 257):
            if not _channels_on_lanes_fits(pad, plan.d, channels):
                continue
            held += 1
            assert resolve_backend("auto", plan, channels,
                                   jnp.float32) == "pallas", (name, channels)
            width = nfft_window.channels_per_call(pad, plan.d, channels)
            assert 1 <= width <= channels
            assert nfft_window.grid_fits_vmem(pad, plan.d, width)
    assert held == 3 * 256  # the old block held all three up to C = 256
    # fig5's grid is the one that needs chunks: 53 channels per call
    # (0.74 MB of grid and 0.52 MB of gather rows each)
    assert nfft_window.channels_per_call(
        padded_grid_size(FIG5.nfft_plan(3)), 3, 256) == 52  # 4 x 52 + 48


_METADATA = re.compile(r", metadata=\{[^}]*\}")
_DEBUG_TABLES = re.compile(r"^FileNames\n.*?(?=^(?:%|ENTRY ))", re.S | re.M)
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _kernel_without_locations(match) -> str:
    """A Pallas kernel's serialized Mosaic module, printed without its
    source locations (which hold the op names of the scopes around it)."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        return json.dumps(module.operation.get_asm(enable_debug_info=False))


def _without_metadata(hlo_text: str) -> str:
    text = _DEBUG_TABLES.sub("", _METADATA.sub("", hlo_text))
    return _KERNEL_BODY.sub(_kernel_without_locations, text)


def _pallas_matvec_text(sharding, n: int = 4096) -> str:
    """The fused matvec at Fig. 5's setup on the Pallas window kernels,
    compiled for the described chip."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    plan = FIG5.nfft_plan(3)
    grid = plan.grid_size
    geometry = WindowGeometry(base=spec((n, 3), jnp.int32),
                              weights=spec((n, 3, plan.taps), jnp.float32),
                              perm=spec((n,), jnp.int32),
                              inv=spec((n,), jnp.int32))
    jax.clear_caches()  # trace anew: a cached jaxpr keeps its op names
    return jax.jit(lambda m, g, x: fused_pipeline(
        plan, m, g, g, x, backend="pallas")).lower(
            spec((grid, grid, grid // 2 + 1), jnp.float32), geometry,
            spec((n, 1), jnp.float32)).compile().as_text()


def test_scopes_leave_the_compiled_pallas_matvec_unchanged(one_chip,
                                                            monkeypatch):
    """The program's scopes (``repro.core.scopes``) around the Pallas
    kernels reach the kernels' serialized modules only as source
    locations: stripped of those and of the op-name metadata, the program
    is the one compiled without the scopes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    scoped = _pallas_matvec_text(one_chip)
    assert scoped.count('custom_call_target="tpu_custom_call"') == 2
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _pallas_matvec_text(one_chip)

    def op_metadata_stripped(text):
        return _DEBUG_TABLES.sub("", _METADATA.sub("", text))

    # the kernels' modules differ, in their source locations alone
    assert op_metadata_stripped(scoped) != op_metadata_stripped(bare)
    assert _without_metadata(scoped) == _without_metadata(bare)
    assert _without_metadata(bare).count("\nENTRY ") == 1


def test_krylov_vectors_keep_a_dense_layout_around_the_kernels(one_chip,
                                                                monkeypatch):
    """A CG solve in the operator's node order hands its vectors to the
    Pallas kernels with no row gather between them.  The kernels take node
    values as rows, one value per 128 lanes at one channel
    (``{1,0:T(8,128)}``); the solve's loop-carried (n, 1) vectors keep a
    dense layout all the same, as they did behind the gathers."""
    import repro.graph.ssl as ssl
    from repro.core import make_kernel, make_normalized_adjacency

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 4096

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def program(points, f):
        op = make_normalized_adjacency(make_kernel("gaussian", sigma=0.75),
                                       points, CRESCENT)
        return ssl.kernel_ssl_cg(op, f, 1e3, tol=1e-4, maxiter=100).u

    with jax.enable_x64(False):
        jax.clear_caches()
        text = jax.jit(program).lower(spec((n, 2), jnp.float32),
                                      spec((n,), jnp.float32)
                                      ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    carried = re.findall(
        r"= f32\[%d,1\]\{([^}]*)\} get-tuple-element" % n, text)
    assert carried and all(layout.startswith("0,1") for layout in carried), \
        carried
