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

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.fastsum import SETUP_2, SETUP_3, FastsumParams
from repro.core.fastsum_exec import resolve_backend
from repro.core.nfft import padded_grid_size
from repro.kernels import nfft_window

FIG5 = FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=1.0 / 8.0)

# (name, nodes, plan): Fig. 5 segmentation (426,400 RGB pixels), and the
# Fig. 3 / SSL accuracy setups at d = 2 and d = 1.
SHAPES = {
    "fig5_d3": (426_400, FIG5.nfft_plan(3)),
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
        args = (spec((pad,) * d + (channels,), jnp.float32), base, weights)
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
    """d=3 SETUP_2: the padded 72^3 grid needs 182 MiB of VMEM after lane
    padding; the rule picks the XLA path, and the compiler agrees that the
    kernel cannot be built there."""
    plan = SETUP_2.nfft_plan(3)
    assert not nfft_window.grid_fits_vmem(padded_grid_size(plan), 3, 1)
    with pytest.raises(Exception, match="(?i)vmem|exceed memory"):
        _compile("gather", 100_000, plan, 1, one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto", plan, 1, jnp.float32) == "xla"
    for n, fits in SHAPES.values():
        assert resolve_backend("auto", fits, 4, jnp.float32) == "pallas"
    # Mosaic has no 64-bit floats: float64 data takes the XLA path on TPU
    assert resolve_backend("auto", FIG5.nfft_plan(3), 1,
                           jnp.float64) == "xla"
