"""Property-based tests for the repro.dist subsystem.

Runs on the single real CPU device: shard_map over a size-1 mesh binds the
axis name without needing multiple devices, so these properties execute in
the main pytest process (the multi-shard behavior is covered by the
``multidevice`` subprocess tests).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import SETUP_1, make_fastsum, make_kernel
from repro.data.synthetic import spiral
from repro.dist.compression import BLOCK, compress_psum
from repro.dist.fastsum_dist import distributed_matvec_fn


def _mesh1():
    return jax.make_mesh((1,), ("data",))


# ---------------------------------------------------------------------------
# compress_psum: idempotence on already-quantized inputs
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 2 ** 31 - 1), exp=st.integers(-8, 8),
       n=st.integers(1, 3 * BLOCK))
def test_compress_psum_idempotent_on_lattice(seed, exp, n):
    """Inputs already on the int8 lattice pass through exactly.

    With a power-of-two scale every quantization step is exact in fp32:
    ``g = ints * 2^exp`` with ``max|int| = 127`` reproduces itself, the
    residual is exactly zero, and (on one shard) the psum-mean equals g.
    """
    rng = np.random.default_rng(seed)
    ints = rng.integers(-127, 128, size=n)
    ints[::BLOCK] = 127  # pin every block's scale to 2^exp exactly
    g = jnp.asarray(ints * (2.0 ** exp), jnp.float32)
    resid = jnp.zeros_like(g)

    mesh = _mesh1()

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def run(gs, rs):
        return compress_psum(gs, "data", rs)

    mean, new_resid = run(g, resid)
    assert bool(jnp.all(mean == g)), "lattice input must survive unchanged"
    assert bool(jnp.all(new_resid == 0.0))

    # and a second round is a fixed point too
    mean2, resid2 = run(mean, new_resid)
    assert bool(jnp.all(mean2 == mean))
    assert bool(jnp.all(resid2 == 0.0))


# ---------------------------------------------------------------------------
# distributed_matvec_fn: linearity + agreement with the local operator
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dist_mv():
    n = 192  # deliberately not divisible by typical shard counts
    points, _ = spiral(n, seed=7)
    pts = jnp.asarray(points, jnp.float32)
    op = make_fastsum(make_kernel("gaussian", sigma=2.5), pts, SETUP_1)
    mesh = _mesh1()
    return op, distributed_matvec_fn(op, mesh, ("data",)), n


@settings(deadline=None, max_examples=10)
@given(a=st.floats(-3, 3), b=st.floats(-3, 3), seed=st.integers(0, 1000))
def test_distributed_matvec_linear(dist_mv, a, b, seed):
    """mv(a*x + b*y) == a*mv(x) + b*mv(y) up to fp32 roundoff."""
    op, mv, n = dist_mv
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    y = jnp.asarray(rng.standard_normal(n), jnp.float32)
    lhs = mv(a * x + b * y)
    rhs = a * mv(x) + b * mv(y)
    scale = float(jnp.max(jnp.abs(rhs))) + float(jnp.max(jnp.abs(lhs))) + 1e-6
    assert float(jnp.max(jnp.abs(lhs - rhs))) / scale < 5e-5


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 1000))
def test_distributed_matvec_matches_local(dist_mv, seed):
    op, mv, n = dist_mv
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    ref = op.matvec(x)
    out = mv(x)
    err = float(jnp.max(jnp.abs(out - ref)) /
                jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))
    assert err < 2e-5, err


def test_distributed_matvec_batched_columns(dist_mv):
    """The drop-in contract includes op.matvec's (n, C) batched form."""
    op, mv, n = dist_mv
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
    ref = op.matvec(x)
    out = mv(x)
    assert out.shape == ref.shape
    err = float(jnp.max(jnp.abs(out - ref)) /
                jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))
    assert err < 2e-5, err
