"""Sharded NFFT fast summation (distributed Algorithm 3.1).

The dense kernel matvec ``y = W̃ x`` factors as

    spread  ->  FFT  ->  spectral multiply  ->  IFFT  ->  gather

and only the spectral accumulation couples nodes across shards.  We shard
the *node* dimension and offer two spectral modes for that one cross-shard
accumulation (``distributed_matvec_fn(..., spectral_mode=...)``):

``"psum"`` (default)
    Each device spreads its local nodes onto the oversampled grid and runs
    the real-to-complex FFT locally; a single ``psum`` over the mesh axes of
    the *support block* of the multiplied half-spectrum (~``N^d/2`` complex,
    independent of ``n``) completes the reduction, and the inverse FFT +
    gather are again purely local.  Per-device spectrum memory and wire
    payload are constant in the mesh size.

``"pencil"``
    The transform itself is sharded (:mod:`repro.dist.pencil_fft`): the
    cross-shard accumulation becomes a ``reduce_scatter`` of the spread grid
    into per-device pencils, the distributed rfftn runs local trailing-axis
    FFTs plus ``all_to_all`` transposes, the spectral multiply hits each
    device's multiplier *slab*, and an ``all_gather`` of the
    inverse-transformed pencils feeds the local window gather.  Per-device
    spectrum memory, FFT flops, and collective payload all scale ~1/P with
    the pencil group size — the regime past ~64 devices where the psum
    payload stops improving.  ``d = 1`` has no trailing axis to keep local
    and falls back to the psum path, as does a mesh where no axis divides
    the grid (a degenerate pencil would psum the full grid — strictly
    worse).

A single operator runs as the bank of one (:mod:`repro.core.fastsum_exec`),
so each spectral mode has one per-shard body, bank-shaped.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fastsum_exec
from repro.core.nfft import NfftPlan, WindowGeometry
from repro.dist import pencil_fft

Array = jax.Array

SPECTRAL_MODES = ("psum", "pencil")


def _psum_body(plan: NfftPlan, mult_bank: Array, geometry: WindowGeometry,
               x: Array, axes: tuple[str, ...],
               backend: str | None = None) -> Array:
    """Per-shard psum-mode body: ONE psum of the multiplied half-spectrum
    restricted to the multipliers' support block (~N^d/2 complex per system
    column: the entire wire payload), injected into the shared pipeline via
    its ``spectral_reduce`` hook.  The S·C system columns ride the channel
    axis, so it stays one collective after one spread and one forward FFT
    per shard."""
    reduce = (lambda block: jax.lax.psum(block, axes)) if axes else None
    return fastsum_exec.fused_pipeline_bank(plan, mult_bank, geometry,
                                            geometry, x,
                                            spectral_reduce=reduce,
                                            backend=backend)


def _pencil_body(plan: NfftPlan, mult_bank: Array, geometry: WindowGeometry,
                 x: Array, spec: pencil_fft.PencilSpec,
                 backend: str | None = None) -> Array:
    """Per-shard pencil-mode body: the ``spectral_op`` hook replaces the
    whole rfftn -> multiply -> irfftn mid-section with the reduce-scattered,
    slab-sharded transform.  Per-device ``(S, slab)`` multiplier slabs (the
    vmapped :func:`pencil_fft.multiplier_slab`) multiply the shared pencil
    spectrum member-wise; one reduce_scatter / all_gather pair moves the
    S·C-channel pencils."""

    def spectral_op(g, bank):
        pencil = pencil_fft.pencil_accumulate(g, spec)
        gh = pencil_fft.pencil_rfftn(pencil, spec)
        slabs = jax.vmap(
            lambda m: pencil_fft.multiplier_slab(m, spec))(bank)
        gh = fastsum_exec.bank_multiply(slabs, gh, broadcast=x.ndim < 3)
        y = pencil_fft.pencil_irfftn(gh, spec)
        return pencil_fft.pencil_allgather(y, spec)

    return fastsum_exec.fused_pipeline_bank(plan, mult_bank, geometry,
                                            geometry, x, backend=backend,
                                            spectral_op=spectral_op)


def resolve_pencil_spec(plan: NfftPlan, mesh, axes, pencil_axes=None):
    """PencilSpec the pencil mode would use, or None when it degenerates.

    None means the psum path runs instead: d = 1 (no trailing axis to keep
    local), or a mesh where no axis divides the grid (a degenerate pencil
    would psum the full grid — strictly worse than the support-block psum).
    Callers that label artifacts by spectral mode should consult this to
    report the *effective* mode.
    """
    if plan.d < 2:
        return None
    spec = pencil_fft.make_pencil_spec(mesh, tuple(axes), plan.grid_size,
                                       plan.d, pencil_axes=pencil_axes)
    return None if spec.row_size * spec.col_size == 1 else spec


# One warning per process when a *requested* pencil mode degenerates: the
# silent psum substitution is correct (same math, one collective) but the
# scaling profile the caller asked for is not what runs — say so once.
_PENCIL_FALLBACK_WARNED = [False]


def _note_pencil_fallback(plan: NfftPlan, mesh) -> None:
    if _PENCIL_FALLBACK_WARNED[0]:
        return
    _PENCIL_FALLBACK_WARNED[0] = True
    warnings.warn(
        f"spectral_mode='pencil' degenerates on this configuration "
        f"(d={plan.d}, grid={plan.grid_size}, mesh shape "
        f"{dict(mesh.shape)}): no mesh axis divides the grid into pencils; "
        "degrading to the support-block psum path (same result, "
        "replicated-spectrum scaling)",
        RuntimeWarning, stacklevel=3)


def make_sharded_matvec(plan: NfftPlan, mesh, axes, *, jit: bool = True,
                        **kw):
    """shard_map'd matvec body ``(mult_half, base, w1d, x) -> y`` (row order).

    Operands 1..3 are sharded along the node dimension over ``axes``; the
    multiplier is replicated.  :func:`make_sharded_matvec_bank` at S = 1,
    broadcast (``kw``: its ``spectral_mode``, ``backend``,
    ``pencil_axes``).  Shared by :func:`distributed_matvec_fn`'s callers
    and the dry-run graph cells, so what the 512-chip cells lower is
    literally the shipped matvec.  ``jit=False`` returns the bare function
    (the dry-run jits it with explicit in_shardings).
    """
    bank_mv = make_sharded_matvec_bank(plan, mesh, axes, lockstep=False,
                                       jit=False, **kw)

    def _mv(mult_half, base_, w_, x_):
        return bank_mv(mult_half[None], base_, w_, x_)[0]

    return jax.jit(_mv) if jit else _mv


def make_sharded_matvec_bank(plan: NfftPlan, mesh, axes, *,
                             lockstep: bool,
                             spectral_mode: str = "psum",
                             backend: str | None = None, pencil_axes=None,
                             jit: bool = True):
    """shard_map'd bank matvec body ``(mult_bank, base, w1d, x) -> y``.

    The multiplier *bank* ``(S,) + half-spectrum`` is replicated, the window
    geometry and the node dimension of ``x`` are sharded over ``axes``, and
    the output is ``(S, rows, C)`` with only the row axis sharded.
    ``lockstep`` is the static input flavor: False takes ``x`` (rows, C)
    (every member applied to the same columns — spread runs with C
    channels), True takes ``x`` (S, rows, C) (member s applied to x[s], the
    bank Krylov shape — the S·C system columns ride the channel axis).
    Either way each shard runs ONE spread and ONE forward transform, and the
    cross-shard accumulation is a single collective: the psum of the stacked
    support blocks, or the pencil reduce_scatter with per-device
    ``(S, slab)`` multiplier slabs.  ``jit=False`` returns the bare
    shard_map'd function.
    """
    axes = tuple(axes)
    if spectral_mode not in SPECTRAL_MODES:
        raise ValueError(
            f"spectral_mode must be one of {SPECTRAL_MODES}, "
            f"got {spectral_mode!r}")
    spec = None
    if spectral_mode == "pencil":
        spec = resolve_pencil_spec(plan, mesh, axes, pencil_axes)
        if spec is None:
            _note_pencil_fallback(plan, mesh)
    x_spec = P(None, axes, None) if lockstep else P(axes, None)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), P(axes, None), P(axes, None, None),
                                 x_spec),
                       out_specs=P(None, axes, None), check_vma=False)
    def _mv(mult_bank, base_, w_, x_):
        # rows are globally Morton-sorted and the caller pre-permutes x, so
        # the per-shard rows already are in order: no perm (no local take
        # and no inverse-permutation scatter, which the TPU compiler cannot
        # build inside shard_map)
        local = WindowGeometry(base=base_, weights=w_, perm=None)
        if spec is not None:
            return _pencil_body(plan, mult_bank, local, x_, spec,
                                backend=backend)
        return _psum_body(plan, mult_bank, local, x_, axes, backend=backend)

    return jax.jit(_mv) if jit else _mv


def _sharded_bank_apply(plan: NfftPlan, win: WindowGeometry, n: int, mesh,
                        axes, **kw):
    """``apply(mult_bank, x)``: the bank kernel sums ``W̃_s`` in node order,
    the node dimension sharded over ``axes`` of ``mesh`` (``kw``: the
    spectral mode, backend and pencil axes of
    :func:`make_sharded_matvec_bank`).

    ``x`` (n,) / (n, C) is broadcast, giving (S, n) / (S, n, C); ``x``
    (S, n, C) is lockstep.  The node count is padded with ghost nodes to a
    multiple of the shard count, so any (n, mesh) combination works: ghost
    rows carry zero window weights (no spread/gather contribution) and
    identity perm entries.  The rows go into the geometry's Morton order
    before the sharded body and come back by a row *take* of the inverse
    permutation (a concrete numpy argsort; the equivalent multi-channel row
    scatter costs ~10x more on XLA CPU).
    """
    pad = (-n) % int(np.prod([mesh.shape[a] for a in axes]))
    base, w1d, perm = win.base, win.weights, win.perm
    if pad:
        base = jnp.pad(base, ((0, pad), (0, 0)))
        w1d = jnp.pad(w1d, ((0, pad), (0, 0), (0, 0)))
        perm = jnp.concatenate(
            [perm, jnp.arange(n, n + pad, dtype=perm.dtype)])
    inv_perm = jnp.asarray(np.argsort(np.asarray(perm)), perm.dtype)
    # the take reads rows from every shard, so its output sharding cannot be
    # inferred from a node-sharded operand; naming it (replicated, like the
    # caller's x) lets the same code run on meshes with Auto and with
    # Explicit axes (jax.make_mesh's default)
    replicated = NamedSharding(mesh, P())
    # both flavors are lazy (jax.jit traces on first call), so building the
    # unused one costs nothing
    bodies = {lockstep: make_sharded_matvec_bank(plan, mesh, axes,
                                                 lockstep=lockstep, **kw)
              for lockstep in (False, True)}

    def apply(mult_bank: Array, x: Array) -> Array:
        xb = x if x.ndim >= 2 else x[:, None]
        if pad:
            xb = jnp.pad(xb, ((0, 0),) * (xb.ndim - 2) + ((0, pad), (0, 0)))
        y_sorted = bodies[x.ndim == 3](mult_bank, base, w1d,
                                       xb[..., perm, :])
        y = y_sorted.at[:, inv_perm].get(out_sharding=replicated)[:, :n]
        return y if x.ndim >= 2 else y[..., 0]

    return apply


def distributed_matvec_fn(op, mesh, axes, *, backend: str | None = None,
                          spectral_mode: str = "psum", pencil_axes=None):
    """Sharded drop-in for ``op.matvec`` (op: :class:`FastsumOperator`).

    Returns ``mv(x)`` computing ``W x = (W̃ - K(0) I) x`` for ``x`` of shape
    (n,) or (n, C), with the node dimension sharded over ``axes`` of
    ``mesh``.  The node count is padded with zero-weight ghost nodes to a
    multiple of the shard count, so any (n, mesh) combination works.
    ``backend`` selects the per-shard window-step backend (default "auto",
    see :func:`repro.core.fastsum_exec.resolve_backend`); ``mesh`` may have
    Auto or Explicit axes; ``spectral_mode`` selects the cross-shard
    spectral accumulation (see module docstring); ``pencil_axes`` optionally
    overrides the pencil row/col mesh-axis split.
    """
    # op.matvec's own contract: the K(0)-diagonal subtraction is only valid
    # when source and target nodes coincide.  A same-length but distinct
    # target set (e.g. the KRR prediction operator) must fail loudly here,
    # not silently evaluate the forward NFFT at the wrong nodes.
    assert op.scaled_tgt is None, \
        "distributed matvec requires src == tgt nodes (shared geometry)"
    apply = _sharded_bank_apply(op.plan, op.src_window, op.n_source, mesh,
                                tuple(axes), spectral_mode=spectral_mode,
                                backend=backend, pencil_axes=pencil_axes)
    out_scale = op.output_scale
    k0 = op.kernel_at_zero

    def matvec(x: Array) -> Array:
        return apply(op.multiplier_half[None], x)[0] * out_scale - k0 * x

    return matvec


def distributed_matvec_bank_fn(bank, mesh, axes, *,
                               backend: str | None = None,
                               spectral_mode: str = "psum",
                               pencil_axes=None):
    """Sharded drop-in for ``bank.matvec`` (bank: ``FastsumOperatorBank``).

    Returns ``mv(x)`` computing ``y[s] = (W̃_s - K_s(0) I) x`` for ``x`` of
    shape (n,) or (n, C) (broadcast), or ``y[s] = (W̃_s - K_s(0) I) x[s]``
    for ``x`` of shape (S, n, C) (lockstep — what a bank Krylov solver
    iterates on), with the node dimension sharded over ``axes`` of ``mesh``.
    Same ghost-node padding, backends, and spectral modes as
    :func:`distributed_matvec_fn`; the one cross-shard collective carries
    the bank stacked into the channel axis.
    """
    assert bank.scaled_tgt is None, \
        "distributed bank matvec requires src == tgt nodes (shared geometry)"
    apply = _sharded_bank_apply(bank.plan, bank.src_window, bank.n_source,
                                mesh, tuple(axes),
                                spectral_mode=spectral_mode,
                                backend=backend, pencil_axes=pencil_axes)
    k0 = bank.kernel_at_zero  # (S,); output scales are folded into the bank

    def matvec(x: Array) -> Array:
        y = apply(bank.multiplier_bank, x)
        return y - k0.reshape((-1,) + (1,) * (y.ndim - 1)) * x

    return matvec
