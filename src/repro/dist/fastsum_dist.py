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

``_spectral_matvec_local`` keeps the seed two-NFFT body (full ``N^d``
psum); it survives only as an oracle.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fastsum_exec, nfft as nfft_mod
from repro.core.nfft import NfftGeometry, NfftPlan, WindowGeometry
from repro.dist import pencil_fft

Array = jax.Array

SPECTRAL_MODES = ("psum", "pencil")


def _spectral_matvec_local(plan: NfftPlan, b_hat: Array,
                           geometry: NfftGeometry, x: Array,
                           axes: tuple[str, ...],
                           tgt_geometry: NfftGeometry | None = None) -> Array:
    """Per-shard body of the seed two-NFFT distributed matvec (oracle only).

    ``geometry``/``x`` hold this shard's slice of the node dimension;
    ``b_hat`` is replicated.  The one cross-shard collective is the psum of
    the adjoint's full ``N^d`` spectral coefficients.
    """
    tgt = geometry if tgt_geometry is None else tgt_geometry
    x_hat = nfft_mod.nfft_adjoint(plan, geometry, x)
    if axes:
        x_hat = jax.lax.psum(x_hat, axes)
    f_hat = b_hat[..., None] * x_hat if x.ndim == 2 else b_hat * x_hat
    f = nfft_mod.nfft_forward(plan, tgt, f_hat)
    return jnp.real(f).astype(x.dtype)


def _fused_matvec_local(plan: NfftPlan, mult_half: Array,
                        geometry: WindowGeometry, x: Array,
                        axes: tuple[str, ...],
                        backend: str | None = None) -> Array:
    """Per-shard psum-mode body of the fused distributed matvec.

    The one cross-shard collective is the psum of the multiplied
    half-spectrum restricted to the multiplier's support block (~N^d/2
    complex: the entire wire payload), injected into the shared
    single-device pipeline via its ``spectral_reduce`` hook.
    """
    reduce = (lambda block: jax.lax.psum(block, axes)) if axes else None
    return fastsum_exec.fused_pipeline(plan, mult_half, geometry, geometry,
                                       x, spectral_reduce=reduce,
                                       backend=backend)


def _pencil_matvec_local(plan: NfftPlan, mult_half: Array,
                         geometry: WindowGeometry, x: Array,
                         spec: pencil_fft.PencilSpec,
                         backend: str | None = None) -> Array:
    """Per-shard pencil-mode body: the ``spectral_op`` hook replaces the
    whole rfftn -> multiply -> irfftn mid-section with the reduce-scattered,
    slab-sharded transform."""

    def spectral_op(g):
        pencil = pencil_fft.pencil_accumulate(g, spec)
        gh = pencil_fft.pencil_rfftn(pencil, spec)
        slab = pencil_fft.multiplier_slab(mult_half, spec)
        gh = gh * slab.astype(gh.dtype)[..., None]
        y = pencil_fft.pencil_irfftn(gh, spec)
        return pencil_fft.pencil_allgather(y, spec).astype(g.dtype)

    return fastsum_exec.fused_pipeline(plan, mult_half, geometry, geometry,
                                       x, backend=backend,
                                       spectral_op=spectral_op)


def _fused_matvec_bank_local(plan: NfftPlan, mult_bank: Array,
                             geometry: WindowGeometry, x: Array,
                             axes: tuple[str, ...],
                             backend: str | None = None) -> Array:
    """Per-shard psum-mode bank body: ONE psum of the *stacked* multiplier
    support blocks (the S·C system columns ride the channel axis, so the
    wire payload is the single-operator support block times S·C — still one
    collective, and still one spread + one forward FFT per shard)."""
    reduce = (lambda block: jax.lax.psum(block, axes)) if axes else None
    return fastsum_exec.fused_pipeline_bank(plan, mult_bank, geometry,
                                            geometry, x,
                                            spectral_reduce=reduce,
                                            backend=backend)


def _pencil_matvec_bank_local(plan: NfftPlan, mult_bank: Array,
                              geometry: WindowGeometry, x: Array,
                              spec: pencil_fft.PencilSpec,
                              backend: str | None = None) -> Array:
    """Per-shard pencil-mode bank body: per-device ``(S, slab)`` multiplier
    slabs (the vmapped :func:`pencil_fft.multiplier_slab`) multiply the
    shared pencil spectrum member-wise; one reduce_scatter / all_gather pair
    moves the S·C-channel pencils."""
    nb = mult_bank.shape[0]
    c = x.shape[-1] if x.ndim >= 2 else 1
    lockstep = x.ndim == 3

    def spectral_op(g):
        pencil = pencil_fft.pencil_accumulate(g, spec)
        gh = pencil_fft.pencil_rfftn(pencil, spec)
        slabs = jax.vmap(
            lambda m: pencil_fft.multiplier_slab(m, spec))(mult_bank)
        slabs = jnp.moveaxis(slabs, 0, -1)  # slab spectrum + (S,)
        if lockstep:
            ghb = gh.reshape(gh.shape[:-1] + (nb, c))
        else:
            ghb = gh[..., None, :]  # broadcast the shared spectrum over S
        prod = slabs[..., :, None].astype(gh.dtype) * ghb
        flat = prod.reshape(prod.shape[:-2] + (nb * c,))
        y = pencil_fft.pencil_irfftn(flat, spec)
        return pencil_fft.pencil_allgather(y, spec).astype(g.dtype)

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


def make_sharded_matvec(plan: NfftPlan, mesh, axes, *,
                        spectral_mode: str = "psum",
                        backend: str | None = None, pencil_axes=None,
                        jit: bool = True):
    """shard_map'd matvec body ``(mult_half, base, w1d, x) -> y`` (row order).

    Operands 1..3 are sharded along the node dimension over ``axes``; the
    multiplier is replicated.  Shared by :func:`distributed_matvec_fn` and
    the dry-run graph cells, so what the 512-chip cells lower is literally
    the shipped matvec.  ``jit=False`` returns the bare shard_map'd function
    (the dry-run jits it with explicit in_shardings).
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

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), P(axes, None), P(axes, None, None),
                                 P(axes, None)),
                       out_specs=P(axes, None), check_vma=False)
    def _mv(mult_half, base_, w_, x_):
        # rows are globally Morton-sorted and the caller pre-permutes x, so
        # the per-shard rows already are in order: no perm (no local take
        # and no inverse-permutation scatter, which the TPU compiler cannot
        # build inside shard_map)
        local = WindowGeometry(base=base_, weights=w_, perm=None)
        if spec is not None:
            return _pencil_matvec_local(plan, mult_half, local, x_, spec,
                                        backend=backend)
        return _fused_matvec_local(plan, mult_half, local, x_, axes,
                                   backend=backend)

    return jax.jit(_mv) if jit else _mv


def make_sharded_matvec_bank(plan: NfftPlan, mesh, axes, *,
                             lockstep: bool,
                             spectral_mode: str = "psum",
                             backend: str | None = None, pencil_axes=None,
                             jit: bool = True):
    """shard_map'd bank matvec body ``(mult_bank, base, w1d, x) -> y``.

    The bank analogue of :func:`make_sharded_matvec`: the multiplier *bank*
    ``(S,) + half-spectrum`` is replicated, the window geometry and the node
    dimension of ``x`` are sharded over ``axes``, and the output is
    ``(S, rows, C)`` with only the row axis sharded.  ``lockstep`` is the
    static input flavor: False takes ``x`` (rows, C) (every member applied
    to the same columns — spread runs with C channels), True takes ``x``
    (S, rows, C) (member s applied to x[s], the bank Krylov shape — the S·C
    system columns ride the channel axis).  Either way each shard runs ONE
    spread and ONE forward transform, and the cross-shard accumulation is a
    single collective: the psum of the stacked support blocks, or the
    pencil reduce_scatter with per-device ``(S, slab)`` multiplier slabs.
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
        local = WindowGeometry(base=base_, weights=w_, perm=None)
        if spec is not None:
            return _pencil_matvec_bank_local(plan, mult_bank, local, x_,
                                             spec, backend=backend)
        return _fused_matvec_bank_local(plan, mult_bank, local, x_, axes,
                                        backend=backend)

    return jax.jit(_mv) if jit else _mv


def _pad_ghost_geometry(win: WindowGeometry, n: int, nshard: int):
    """Ghost-pad a window geometry so the node dimension shards evenly.

    Ghost rows carry zero window weights (no spread/gather contribution)
    and identity perm entries.  Returns ``(base, w1d, perm, inv_perm,
    pad)``; ``inv_perm`` (a concrete numpy argsort) lets callers unsort
    results with a row *take* — the equivalent multi-channel row scatter
    costs ~10x more on XLA CPU.
    """
    pad = (-n) % nshard
    base, w1d, perm = win.base, win.weights, win.perm
    if pad:
        base = jnp.pad(base, ((0, pad), (0, 0)))
        w1d = jnp.pad(w1d, ((0, pad), (0, 0), (0, 0)))
        perm = jnp.concatenate(
            [perm, jnp.arange(n, n + pad, dtype=perm.dtype)])
    inv_perm = jnp.asarray(np.argsort(np.asarray(perm)), perm.dtype)
    return base, w1d, perm, inv_perm, pad


def _unsort_rows(y_sorted: Array, inv_perm: Array, mesh, axis: int) -> Array:
    """Row take back into node order, replicated over ``mesh``.

    The take reads rows from every shard, so its output sharding cannot be
    inferred from a node-sharded operand; naming it (replicated, like the
    caller's ``x``) lets the same code run on meshes with Auto and with
    Explicit axes (``jax.make_mesh``'s default).
    """
    idx = (slice(None),) * axis + (inv_perm,)
    return y_sorted.at[idx].get(out_sharding=NamedSharding(mesh, P()))


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
    plan = op.plan
    axes = tuple(axes)
    # op.matvec's own contract: the K(0)-diagonal subtraction is only valid
    # when source and target nodes coincide.  A same-length but distinct
    # target set (e.g. the KRR prediction operator) must fail loudly here,
    # not silently evaluate the forward NFFT at the wrong nodes.
    assert op.scaled_tgt is None, \
        "distributed matvec requires src == tgt nodes (shared geometry)"
    assert op.multiplier_half is not None and op.src_window is not None, \
        "distributed matvec requires a fused operator (build via make_fastsum)"
    n = op.n_source
    nshard = int(np.prod([mesh.shape[a] for a in axes]))
    base, w1d, perm, inv_perm, pad = _pad_ghost_geometry(
        op.src_window, n, nshard)

    _mv = make_sharded_matvec(plan, mesh, axes, spectral_mode=spectral_mode,
                              backend=backend, pencil_axes=pencil_axes)

    out_scale = op.output_scale
    k0 = op.kernel_at_zero

    def matvec(x: Array) -> Array:
        batched = x.ndim == 2
        xp = x if batched else x[:, None]
        if pad:
            xp = jnp.pad(xp, ((0, pad), (0, 0)))
        y_sorted = _mv(op.multiplier_half, base, w1d, xp[perm])
        y = _unsort_rows(y_sorted, inv_perm, mesh, 0)
        if pad:
            y = y[:n]
        if not batched:
            y = y[..., 0]
        return y * out_scale - k0 * x

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
    plan = bank.plan
    axes = tuple(axes)
    assert bank.scaled_tgt is None, \
        "distributed bank matvec requires src == tgt nodes (shared geometry)"
    n = bank.n_source
    nshard = int(np.prod([mesh.shape[a] for a in axes]))
    base, w1d, perm, inv_perm, pad = _pad_ghost_geometry(
        bank.src_window, n, nshard)

    kw = dict(spectral_mode=spectral_mode, backend=backend,
              pencil_axes=pencil_axes)
    # both flavors are lazy (jax.jit traces on first call), so building the
    # unused one costs nothing
    _mv_bcast = make_sharded_matvec_bank(plan, mesh, axes, lockstep=False,
                                         **kw)
    _mv_lock = make_sharded_matvec_bank(plan, mesh, axes, lockstep=True,
                                        **kw)
    k0 = bank.kernel_at_zero  # (S,); output scales are folded into the bank

    def matvec(x: Array) -> Array:
        lockstep = x.ndim == 3
        if lockstep:
            xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
            y_sorted = _mv_lock(bank.multiplier_bank, base, w1d, xp[:, perm])
        else:
            batched = x.ndim == 2
            xb = x if batched else x[:, None]
            xp = jnp.pad(xb, ((0, pad), (0, 0))) if pad else xb
            y_sorted = _mv_bcast(bank.multiplier_bank, base, w1d, xp[perm])
        y = _unsort_rows(y_sorted, inv_perm, mesh, 1)
        if pad:
            y = y[:, :n]
        if lockstep:
            return y - k0[:, None, None] * x
        if not batched:
            return y[..., 0] - k0[:, None] * x
        return y - k0[:, None, None] * x[None]

    return matvec
