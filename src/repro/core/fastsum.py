"""NFFT-based fast summation — Algorithms 3.1 and 3.2 of the paper.

Algorithm 3.1 computes, for a rotation-invariant kernel ``K`` and nodes
``v_j``, the dense kernel sums

    (W̃ x)_j = sum_i x_i K(v_j - v_i)            (diagonal = K(0))

in ``O(n)`` for fixed accuracy:  adjoint NFFT -> multiply by the kernel
Fourier coefficients ``b_hat`` -> forward NFFT.  Separate source/target node
sets are supported (used by the NFFT kernel-attention decode path).

Algorithm 3.2 wraps this into the normalized adjacency operator
``A = D^{-1/2} W D^{-1/2}`` with ``D = diag(W 1)`` and ``W = W̃ - K(0) I``,
including the node rescaling by the correction factor ``rho``.

Note on multiquadric output scaling (Alg. 3.2 steps 4/5): the paper says
"scale output by rho for multiquadric and 1/rho for inverse multiquadric";
direct computation shows K_{c*rho}(rho*y) = rho * K_c(y) for the multiquadric
(so the output must be scaled by 1/rho) and = (1/rho) * K_c(y) for the
inverse multiquadric (scale by rho).  We implement the sign that our oracle
tests verify; see Kernel.output_scale_exponent.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fastsum_exec, nfft as nfft_mod, scopes
from repro.core.kernels import Kernel
from repro.core.node_order import NodeOrder
from repro.core.nfft import (
    NfftGeometry, NfftPlan, WindowGeometry, build_geometry,
    build_window_geometry,
)
from repro.core.regularization import kernel_fourier_coefficients

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FastsumParams:
    """Static fast-summation accuracy parameters (Figure 1 of the paper)."""

    n_bandwidth: int  # N
    m: int  # NFFT window cut-off
    p: int | None = None  # regularization smoothness (default: m)
    eps_b: float | None = None  # regularization region (default: p/N)
    sigma_os: float = 2.0
    window: str = nfft_mod.KAISER_BESSEL

    @property
    def p_eff(self) -> int:
        return self.m if self.p is None else self.p

    @property
    def eps_b_eff(self) -> float:
        return self.p_eff / self.n_bandwidth if self.eps_b is None else self.eps_b

    def nfft_plan(self, d: int) -> NfftPlan:
        return NfftPlan(d=d, n_bandwidth=self.n_bandwidth, m=self.m,
                        sigma_os=self.sigma_os, window=self.window)


# The paper's three accuracy tiers (Section 6.1).
SETUP_1 = FastsumParams(n_bandwidth=16, m=2, eps_b=0.0)
SETUP_2 = FastsumParams(n_bandwidth=32, m=4, eps_b=0.0)
SETUP_3 = FastsumParams(n_bandwidth=64, m=7, eps_b=0.0)


def scale_nodes(points: Array, eps_b: float, *, center: bool = True):
    """Shift/scale raw data into the admissible ball (Alg. 3.2 step 1).

    Returns (scaled_nodes, rho, shift): ``scaled = (points - shift) * rho``
    with ``||scaled||_2 <= 1/4 - eps_b/2``.

    Non-finite coordinates are rejected at plan time: a single NaN node
    would poison the min/max centering, collapse ``rho`` to NaN, and
    silently corrupt the Morton geometry and every operator planned from
    it.  (The check only runs on concrete arrays — all planners call this
    eagerly — so traced callers are unaffected.)
    """
    if not isinstance(points, jax.core.Tracer) and \
            not bool(jnp.all(jnp.isfinite(points))):
        raise ValueError(
            "non-finite coordinates in the point set; scrub the data or "
            "drop the offending nodes before planning")
    if center:
        lo = jnp.min(points, axis=0)
        hi = jnp.max(points, axis=0)
        shift = (lo + hi) / 2.0
    else:
        shift = jnp.zeros((points.shape[1],), points.dtype)
    centered = points - shift
    max_norm = jnp.max(jnp.linalg.norm(centered, axis=1))
    target = 0.25 - eps_b / 2.0
    rho = target / jnp.maximum(max_norm, jnp.finfo(points.dtype).tiny)
    return centered * rho, rho, shift


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FastsumOperator:
    """Algorithm 3.1 as a linear operator  x -> W̃ x  (+ optional targets).

    Build with :func:`make_fastsum`.  ``matvec`` maps (n_src,) [or
    (n_src, C)] real vectors to (n_tgt,) [or (n_tgt, C)] real outputs.
    """

    plan: NfftPlan  # static
    b_hat: Array
    scaled_src: Array  # (n_src, d) nodes in the admissible ball
    scaled_tgt: Array  # (n_tgt, d), or None when targets == sources
    output_scale: Array  # rho**exponent correction (scalar)
    kernel_at_zero: Array  # K(0) for the *rescaled* kernel, already corrected
    # Fused-engine state (plan-once): combined spectral multiplier on the
    # oversampled half-spectrum + separable Morton-sorted window geometry.
    multiplier_half: Array
    src_window: WindowGeometry
    tgt_window: WindowGeometry
    # Re-spectralization state: the admissible-ball scale factor and the
    # accuracy parameters the operator was planned with.  Geometry (points,
    # rho, Morton windows) is fixed plan-time data with zero cotangents; the
    # spectral children above are the param-dependent, differentiable half —
    # :meth:`with_kernel` rebuilds exactly those for a new (possibly traced)
    # kernel without replanning.
    rho: Array = None
    fs_params: FastsumParams = None  # static

    def tree_flatten(self):
        children = (self.b_hat, self.scaled_src, self.scaled_tgt,
                    self.output_scale, self.kernel_at_zero,
                    self.multiplier_half, self.src_window, self.tgt_window,
                    self.rho)
        return children, (self.plan, self.fs_params)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], *children, fs_params=aux[1])

    def with_kernel(self, kernel: Kernel) -> "FastsumOperator":
        """Same plan/geometry, new kernel: rebuild only the spectral data.

        Jit/grad-safe: ``kernel`` may carry traced parameters (sigma/c), in
        which case the returned operator's ``b_hat`` / ``multiplier_half`` /
        ``output_scale`` / ``kernel_at_zero`` are traced functions of them —
        the seam gradient-based model selection differentiates through.
        """
        if self.rho is None or self.fs_params is None:
            raise ValueError(
                "with_kernel needs the planning state (rho, fs_params); "
                "this operator was built by hand or by an older path — "
                "re-plan it with make_fastsum")
        b_hat, mult_half, out_scale, k0_corr = _member_spectral(
            kernel, self.rho, self.plan, self.fs_params)
        rdt = jnp.real(b_hat).dtype
        return dataclasses.replace(
            self, b_hat=b_hat, multiplier_half=mult_half,
            output_scale=jnp.asarray(out_scale, dtype=rdt),
            kernel_at_zero=jnp.asarray(k0_corr, dtype=rdt))

    @property
    def n_source(self) -> int:
        return self.scaled_src.shape[0]

    @property
    def n_target(self) -> int:
        return self.n_source if self.scaled_tgt is None else self.scaled_tgt.shape[0]

    def _cached_geometry(self, attr: str, nodes: Array) -> NfftGeometry:
        geom = self.__dict__.get(attr)
        if geom is None:
            geom = build_geometry(self.plan, nodes)
            if not isinstance(geom.indices, jax.core.Tracer):
                self.__dict__[attr] = geom  # never cache traced values
        return geom

    @property
    def src_geometry(self) -> NfftGeometry:
        """O(n*taps^d) tensor-product geometry, built lazily.

        Only the two-NFFT oracle path reads it; the fused hot path runs on
        the O(n*d*taps) ``src_window``, so operators that never call the
        reference matvec never pay the build time or memory.
        """
        return self._cached_geometry("_src_geom", self.scaled_src)

    @property
    def tgt_geometry(self) -> NfftGeometry:
        if self.scaled_tgt is None:
            return self.src_geometry
        return self._cached_geometry("_tgt_geom", self.scaled_tgt)

    def matvec_tilde(self, x: Array, *, backend: str | None = None) -> Array:
        """y = W̃ x  (diagonal K(0) included) — fused rfftn pipeline.

        ``backend`` selects the window-step backend ("auto"/"xla"/"pallas",
        see :func:`repro.core.fastsum_exec.resolve_backend`).
        """
        f = fastsum_exec.fused_matvec_tilde(
            self.plan, self.multiplier_half, self.src_window,
            self.tgt_window, x, backend=backend)
        return f * self.output_scale

    def matvec_tilde_reference(self, x: Array) -> Array:
        """Seed two-NFFT path (adjoint -> multiply -> forward); the oracle
        the fused engine is tested against, and the benchmark baseline."""
        x_hat = nfft_mod.nfft_adjoint(self.plan, self.src_geometry, x)
        f_hat = self.b_hat[..., None] * x_hat if x.ndim == 2 else self.b_hat * x_hat
        f = nfft_mod.nfft_forward(self.plan, self.tgt_geometry, f_hat)
        return jnp.real(f) * self.output_scale

    def _require_square(self, name: str) -> None:
        if self.scaled_tgt is not None:
            raise ValueError(
                f"FastsumOperator.{name} subtracts the K(0) diagonal, which "
                "is only defined when source and target nodes coincide; this "
                "operator was built with target_points — use matvec_tilde "
                "for rectangular kernel sums.")

    def matvec(self, x: Array, *, backend: str | None = None) -> Array:
        """y = W x = (W̃ - K(0) I) x.  Requires src == tgt nodes."""
        self._require_square("matvec")
        return self.matvec_tilde(x, backend=backend) - self.kernel_at_zero * x

    def matvec_reference(self, x: Array) -> Array:
        """Two-NFFT W x (oracle/baseline counterpart of :meth:`matvec`)."""
        self._require_square("matvec_reference")
        return self.matvec_tilde_reference(x) - self.kernel_at_zero * x

    def degrees(self) -> Array:
        """d = W 1 (row sums of the zero-diagonal weight matrix)."""
        ones = jnp.ones((self.n_source,), dtype=jnp.real(self.b_hat).dtype)
        return self.matvec(ones)

    def node_order(self) -> NodeOrder | None:
        """The Morton order of the window geometry, for a Krylov solve to
        run in (:mod:`repro.core.node_order`); ``None`` for a rectangular
        operator or one with no Morton geometry.

        Its ``matvec`` is :meth:`matvec` of this operator on the rows'
        geometry (:meth:`WindowGeometry.in_row_order`): the same fused
        pipeline, multiplier and diagonal, with the value permutations
        left out.
        """
        win = self.src_window
        if self.scaled_tgt is not None or win.perm is None:
            return None
        rows = win.in_row_order()
        in_rows = dataclasses.replace(self, src_window=rows, tgt_window=rows)
        return NodeOrder(perm=win.perm, inv=win.inv, matvec=in_rows.matvec)


def _scaled_plan(points: Array, params: FastsumParams,
                 target_points: Optional[Array]):
    """Kernel-independent plan-time setup, shared by single operators and
    banks: node scaling into the admissible ball, the NFFT plan, and the
    Morton-sorted window geometries.

    Returns ``(scaled_src, scaled_tgt_or_None, rho, plan, src_win,
    tgt_win)``.
    """
    d = points.shape[1]
    eps_b = params.eps_b_eff
    with jax.named_scope(scopes.BUILD):
        if target_points is None:
            scaled, rho, shift = scale_nodes(points, eps_b)
            scaled_src = scaled_tgt = scaled
        else:
            both = jnp.concatenate([points, target_points], axis=0)
            scaled, rho, shift = scale_nodes(both, eps_b)
            scaled_src = scaled[: points.shape[0]]
            scaled_tgt = scaled[points.shape[0]:]
        plan = params.nfft_plan(d)
        src_win = build_window_geometry(plan, scaled_src)
        tgt_win = src_win if target_points is None \
            else build_window_geometry(plan, scaled_tgt)
    return (scaled_src, None if target_points is None else scaled_tgt,
            rho, plan, src_win, tgt_win)


def _member_spectral(kernel: Kernel, rho, plan: NfftPlan,
                     params: FastsumParams):
    """Per-kernel spectral data: ``(b_hat, mult_half, out_scale, k0_corr)``.

    The only kernel-dependent plan-time work — everything else
    (:func:`_scaled_plan`) is shared across a bank's members.
    """
    # rho may be a concrete scalar (eager planning) or a tracer (operator
    # construction / re-spectralization under jit or grad) — Kernel carries
    # traced parameters natively, so no concretization is needed here.
    with jax.named_scope(scopes.BUILD):
        rescaled_kernel = kernel.rescaled(rho)
        b_hat = kernel_fourier_coefficients(rescaled_kernel, plan.d,
                                            params.n_bandwidth, params.p_eff,
                                            params.eps_b_eff)
        mult_half = fastsum_exec.fused_spectral_multiplier(plan, b_hat)
        exponent = kernel.output_scale_exponent
        out_scale = rho ** exponent if exponent != 0 else 1.0
        # K(0) is scale-invariant for all four kernels w/ parameter
        # rescaling *except* the multiquadrics, where K(0)=c resp. 1/c;
        # out_scale * K_rescaled(0) == K(0) holds for all four — use that:
        k0_corr = out_scale * rescaled_kernel.at_zero()
    return b_hat, mult_half, out_scale, k0_corr


def make_fastsum(
    kernel: Kernel,
    points: Array,
    params: FastsumParams,
    *,
    target_points: Optional[Array] = None,
) -> FastsumOperator:
    """Set up Algorithm 3.1 for ``points`` (n, d) in original coordinates."""
    scaled_src, scaled_tgt, rho, plan, src_win, tgt_win = _scaled_plan(
        points, params, target_points)
    b_hat, mult_half, out_scale, k0_corr = _member_spectral(
        kernel, rho, plan, params)
    rdt = jnp.real(b_hat).dtype
    return FastsumOperator(
        plan=plan,
        b_hat=b_hat,
        scaled_src=scaled_src,
        scaled_tgt=scaled_tgt,
        output_scale=jnp.asarray(out_scale, dtype=rdt),
        kernel_at_zero=jnp.asarray(k0_corr, dtype=rdt),
        multiplier_half=mult_half,
        src_window=src_win,
        tgt_window=tgt_win,
        rho=jnp.asarray(rho),
        fs_params=params,
    )


@dataclasses.dataclass(frozen=True)
class PredictionPlan:
    """Plan-once serving frame: a fixed node scaling over train ∪ domain.

    :func:`make_fastsum` with ``target_points`` rescales the *union* of
    sources and targets into the admissible ball, so the scale factor
    ``rho`` — and with it the rescaled kernel, its Fourier coefficients,
    and the fused spectral multiplier — depends on the target set.  That is
    fine for a one-shot predict, but it makes every new target set a full
    replan, which is exactly what a serving tick cannot afford.

    A ``PredictionPlan`` instead freezes ``(rho, shift)`` over the training
    points plus a declared serving *domain* (default: the training bounding
    box expanded by ``margin``).  Any query set inside the domain is then
    admissible under the frozen scaling, and serving it costs only an O(m)
    target window geometry (:meth:`target_window`) — the NFFT plan, source
    geometry, and every kernel's spectral multiplier
    (:func:`prediction_multiplier`) are reusable verbatim.  One plan is
    shared by every model fitted on the same training points (the
    multi-tenant group of the graph-predict engine).
    """

    plan: NfftPlan
    scaled_src: Array  # (n, d) training nodes under the frozen scaling
    src_window: WindowGeometry
    rho: float
    shift: np.ndarray  # (d,) — plain numpy so the plan hashes/pickles
    radius: float  # admissible ball radius for scaled nodes

    @property
    def n_source(self) -> int:
        return self.scaled_src.shape[0]

    def scale_targets(self, query_points: Array) -> Array:
        """Map raw query points into the frozen scaled frame."""
        q = jnp.asarray(query_points)
        return (q - jnp.asarray(self.shift, q.dtype)) * self.rho

    def admissible(self, scaled_targets: Array, *,
                   slack: float = 1e-9) -> Array:
        """Per-row mask: does a scaled query point fit the admissible ball?

        Points outside wrap around the torus the NFFT periodizes over and
        produce garbage kernel sums — callers must reject them (the serving
        engine fails such requests instead of serving wrong values).
        """
        return jnp.linalg.norm(scaled_targets, axis=-1) <= self.radius + slack

    def target_window(self, scaled_targets: Array) -> WindowGeometry:
        """O(m) per-tick work: window geometry for (already scaled) targets."""
        return build_window_geometry(self.plan, scaled_targets)


def _domain_corners(points: np.ndarray, margin: float) -> np.ndarray:
    """2^d corners of the training bounding box expanded by ``margin``."""
    lo, hi = points.min(axis=0), points.max(axis=0)
    mid, half = (lo + hi) / 2.0, np.maximum((hi - lo) / 2.0, 1e-12)
    half = half * (1.0 + margin)
    d = points.shape[1]
    corners = np.stack(np.meshgrid(*[[-1.0, 1.0]] * d, indexing="ij"),
                       axis=-1).reshape(-1, d)
    return mid[None, :] + corners * half[None, :]


def make_prediction_plan(points: Array, params: FastsumParams, *,
                         domain_points: Optional[Array] = None,
                         margin: float = 0.5) -> PredictionPlan:
    """Kernel-independent serving plan over ``points`` (n, d).

    ``domain_points`` declares the region query points may come from; when
    omitted it defaults to the training bounding box expanded by ``margin``
    per dimension.  The admissible-ball scaling is computed once over
    train ∪ domain and frozen, so serving never replans (see
    :class:`PredictionPlan`).
    """
    pts = jnp.asarray(points)
    if domain_points is None:
        domain = jnp.asarray(_domain_corners(np.asarray(pts), margin),
                             pts.dtype)
    else:
        domain = jnp.asarray(domain_points, pts.dtype)
    both = jnp.concatenate([pts, domain.reshape(-1, pts.shape[1])], axis=0)
    scaled, rho, shift = scale_nodes(both, params.eps_b_eff)
    scaled_src = scaled[: pts.shape[0]]
    plan = params.nfft_plan(pts.shape[1])
    return PredictionPlan(
        plan=plan,
        scaled_src=scaled_src,
        src_window=build_window_geometry(plan, scaled_src),
        rho=float(rho),
        shift=np.asarray(shift),
        radius=0.25 - params.eps_b_eff / 2.0,
    )


def prediction_multiplier(kernel: Kernel, pred: PredictionPlan,
                          params: FastsumParams) -> Array:
    """Fused serving multiplier for one kernel on a shared prediction plan.

    The ``rho**exponent`` output correction is folded in (the pipeline is
    linear), so gathered predictions need no per-column post-scaling —
    mirroring :func:`make_fastsum_bank`'s folded per-member multipliers.
    """
    _, mult_half, out_scale, _ = _member_spectral(
        kernel, pred.rho, pred.plan, params)
    return mult_half * out_scale


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FastsumOperatorBank:
    """A bank of S Algorithm 3.1 operators sharing nodes, plan, and geometry.

    The members differ only in their kernel (and hence spectral multiplier);
    the plan and Morton-sorted window geometry depend only on the points, so
    a bank matvec shares one spread and one forward rfftn across all S
    members (:func:`repro.core.fastsum_exec.fused_pipeline_bank`).  This is
    the execution shape of a hyperparameter sweep (one operator per sigma)
    and of multilayer graphs (one operator per layer kernel).

    Per-member output scales are folded into ``multiplier_bank`` and
    ``b_hat_bank`` at build time (the pipeline is linear), so ``matvec``
    needs no per-member post-scaling and a fixed-weight mixture collapses to
    a plain weighted sum of multipliers (:meth:`mixture`).
    """

    plan: NfftPlan  # static
    b_hat_bank: Array  # (S,) + (N,)*d, output scale folded in
    scaled_src: Array
    scaled_tgt: Array  # or None when targets == sources
    kernel_at_zero: Array  # (S,) corrected K(0) per member
    multiplier_bank: Array  # (S,) + half-spectrum, output scale folded in
    src_window: WindowGeometry
    tgt_window: WindowGeometry

    def tree_flatten(self):
        children = (self.b_hat_bank, self.scaled_src, self.scaled_tgt,
                    self.kernel_at_zero, self.multiplier_bank,
                    self.src_window, self.tgt_window)
        return children, (self.plan,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], *children)

    @property
    def size(self) -> int:
        return self.multiplier_bank.shape[0]

    @property
    def n_source(self) -> int:
        return self.scaled_src.shape[0]

    def member(self, s: int) -> FastsumOperator:
        """Single-member view (a plain :class:`FastsumOperator`).

        Shares the bank's geometry arrays; the member's output scale is
        already folded into its multiplier, so ``output_scale`` is 1.
        """
        one = jnp.ones((), jnp.real(self.b_hat_bank).dtype)
        return FastsumOperator(
            plan=self.plan, b_hat=self.b_hat_bank[s],
            scaled_src=self.scaled_src, scaled_tgt=self.scaled_tgt,
            output_scale=one, kernel_at_zero=self.kernel_at_zero[s],
            multiplier_half=self.multiplier_bank[s],
            src_window=self.src_window, tgt_window=self.tgt_window)

    def mixture(self, weights) -> FastsumOperator:
        """Collapse a fixed-weight mixture ``sum_s w_s W̃_s`` to ONE operator.

        The combined multiplier is the weighted sum of the member
        multipliers, so the whole mixture — e.g. an aggregated multilayer
        Laplacian's weighted sum of per-layer kernels — costs exactly one
        fused matvec per application, not S.
        """
        w = jnp.asarray(weights, jnp.real(self.b_hat_bank).dtype)
        if w.shape != (self.size,):
            raise ValueError(f"weights must have shape ({self.size},), "
                             f"got {w.shape}")
        one = jnp.ones((), w.dtype)
        return FastsumOperator(
            plan=self.plan,
            b_hat=jnp.tensordot(w.astype(self.b_hat_bank.dtype),
                                self.b_hat_bank, axes=1),
            scaled_src=self.scaled_src, scaled_tgt=self.scaled_tgt,
            output_scale=one,
            kernel_at_zero=jnp.dot(w, self.kernel_at_zero),
            multiplier_half=jnp.tensordot(
                w.astype(self.multiplier_bank.dtype), self.multiplier_bank,
                axes=1),
            src_window=self.src_window, tgt_window=self.tgt_window)

    def matvec_tilde(self, x: Array, *, backend: str | None = None) -> Array:
        """Bank kernel sums (diagonal K(0) included).

        ``x`` (n,) / (n, C): broadcast — every member applied to the same
        right-hand sides, returning (S, n) / (S, n, C).  ``x`` (S, n, C):
        lockstep — member ``s`` applied to ``x[s]`` (the bank Krylov shape).
        Either way: one spread, one forward rfftn, one batched irfftn, one
        gather.
        """
        return fastsum_exec.fused_matvec_tilde_bank(
            self.plan, self.multiplier_bank, self.src_window,
            self.tgt_window, x, backend=backend)

    def matvec_tilde_columns(self, u: Array, *,
                             backend: str | None = None) -> Array:
        """Lockstep bank matvec in flat column layout: (n, S*C) -> (n, S*C).

        Column ``s*C + j`` belongs to member ``s`` (bank-major) — the
        layout the per-column solvers iterate on.  Identical math to the
        (S, n, C) lockstep flavor with zero bank-axis transposes per call;
        :func:`repro.graph.krr.krr_fit_sweep` runs its whole CG on this.
        """
        return fastsum_exec.fused_matvec_tilde_bank_columns(
            self.plan, self.multiplier_bank, self.src_window,
            self.tgt_window, u, backend=backend)

    def _require_square(self, name: str) -> None:
        if self.scaled_tgt is not None:
            raise ValueError(
                f"FastsumOperatorBank.{name} subtracts the K(0) diagonal, "
                "which is only defined when source and target nodes "
                "coincide; this bank was built with target_points — use "
                "matvec_tilde for rectangular kernel sums.")

    def matvec(self, x: Array, *, backend: str | None = None) -> Array:
        """y[s] = (W̃_s - K_s(0) I) x  (or x[s] in lockstep flavor)."""
        self._require_square("matvec")
        out = self.matvec_tilde(x, backend=backend)  # (S, n[, C])
        # k0 aligned with out's bank axis broadcasts against both the
        # broadcast (x: (n[, C])) and lockstep (x: (S, n, C)) flavors
        k0 = self.kernel_at_zero.reshape((self.size,) + (1,) * (out.ndim - 1))
        return out - k0 * x


def make_fastsum_bank(
    kernels,
    points: Array,
    params: FastsumParams,
    *,
    target_points: Optional[Array] = None,
) -> FastsumOperatorBank:
    """Plan a bank of Algorithm 3.1 operators over shared ``points``.

    ``kernels`` is a sequence of :class:`~repro.core.kernels.Kernel` — one
    member per kernel/parameter combination (a sigma sweep, the per-layer
    kernels of a multilayer graph, ...).  Node scaling, the NFFT plan, and
    the window geometries are computed once; only the O(N^d) spectral
    multipliers are per-member.
    """
    kernels = tuple(kernels)
    if not kernels:
        raise ValueError("make_fastsum_bank needs at least one kernel")
    scaled_src, scaled_tgt, rho, plan, src_win, tgt_win = _scaled_plan(
        points, params, target_points)

    b_hats, mults, k0s = [], [], []
    for kernel in kernels:
        b_hat, mult_half, out_scale, k0_corr = _member_spectral(
            kernel, rho, plan, params)
        # fold the rho**exponent output correction into the (linear)
        # spectral data so bank members need no per-member post-scale
        b_hats.append(b_hat * out_scale)
        mults.append(mult_half * out_scale)
        k0s.append(k0_corr)
    b_hat_bank = jnp.stack(b_hats)
    return FastsumOperatorBank(
        plan=plan,
        b_hat_bank=b_hat_bank,
        scaled_src=scaled_src,
        scaled_tgt=scaled_tgt,
        kernel_at_zero=jnp.stack(
            [jnp.asarray(k) for k in k0s]).astype(
                jnp.real(b_hat_bank).dtype),
        multiplier_bank=jnp.stack(mults),
        src_window=src_win,
        tgt_window=tgt_win,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NormalizedAdjacencyOperator:
    """Algorithm 3.2:  x -> A x,  A = D^{-1/2} W D^{-1/2} (exactly symmetric).

    Also exposes the graph Laplacian ``L_s x = x - A x`` and the row-stochastic
    ``L_w``-style matvec ``P x = D^{-1} W x`` (used by NFFT kernel attention).
    """

    fastsum: FastsumOperator
    inv_sqrt_deg: Array  # (n,)
    degrees: Array  # (n,)
    # D^{-1/2} in the fastsum's Morton row order (:meth:`node_order`), made
    # by the build's degree pass; None when the fastsum has no node order
    inv_sqrt_deg_rows: Array = None

    def tree_flatten(self):
        return (self.fastsum, self.inv_sqrt_deg, self.degrees,
                self.inv_sqrt_deg_rows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n(self) -> int:
        return self.inv_sqrt_deg.shape[0]

    def matvec(self, x: Array) -> Array:
        scale = self.inv_sqrt_deg if x.ndim == 1 else self.inv_sqrt_deg[:, None]
        return scale * self.fastsum.matvec(scale * x)

    def laplacian_matvec(self, x: Array) -> Array:
        return x - self.matvec(x)

    def stochastic_matvec(self, x: Array) -> Array:
        inv_deg = self.inv_sqrt_deg ** 2
        scale = inv_deg if x.ndim == 1 else inv_deg[:, None]
        return scale * self.fastsum.matvec(x)

    def node_order(self) -> NodeOrder | None:
        """The fastsum's Morton node order, with ``A`` applied in it
        (:mod:`repro.core.node_order`); ``None`` where the build made no
        ``D^{-1/2}`` in that order."""
        s = self.inv_sqrt_deg_rows
        if s is None:
            return None
        order = self.fastsum.node_order()
        fs_rows = order.matvec

        def matvec(x):
            scale = s if x.ndim == 1 else s[:, None]
            return scale * fs_rows(scale * x)

        return order._replace(matvec=matvec)


def _clamped(deg: Array) -> Array:
    # Lemma 3.1 requires eps < eta, i.e. the approximation error below the
    # smallest degree; negative approximate degrees would make D^{-1/2}
    # imaginary (the classical-Nyström failure mode the paper highlights).
    return jnp.maximum(deg, jnp.finfo(deg.dtype).tiny)


def _normalized_adjacency_from(fs: FastsumOperator) -> NormalizedAdjacencyOperator:
    node_order = getattr(fs, "node_order", None)
    order = None if node_order is None else node_order()
    with jax.named_scope(scopes.BUILD):
        if order is None:
            deg, rows = _clamped(fs.degrees()), None
        else:
            # the degree pass in the rows' order (a vector of ones is the
            # same in every order), D^{-1/2} kept in it for the solves that
            # run there, and the degrees mapped back once
            ones = jnp.ones((fs.n_source,), dtype=jnp.real(fs.b_hat).dtype)
            deg_rows = _clamped(order.matvec(ones))
            deg, rows = deg_rows[order.inv], 1.0 / jnp.sqrt(deg_rows)
        inv_sqrt_deg = 1.0 / jnp.sqrt(deg)
    return NormalizedAdjacencyOperator(
        fastsum=fs, inv_sqrt_deg=inv_sqrt_deg, degrees=deg,
        inv_sqrt_deg_rows=rows)


def make_normalized_adjacency(
    kernel: Kernel, points: Array, params: FastsumParams
) -> NormalizedAdjacencyOperator:
    return _normalized_adjacency_from(make_fastsum(kernel, points, params))


def make_normalized_adjacency_mixture(
    kernels, weights, points: Array, params: FastsumParams
) -> NormalizedAdjacencyOperator:
    """Algorithm 3.2 for an aggregated multilayer weight matrix.

    The multilayer extension (Bergermann–Stoll–Volkmer 2020) aggregates the
    per-layer kernels into ``W = sum_l w_l (W̃_l - K_l(0) I)`` before
    normalizing.  The mixture collapses to a *single* summed spectral
    multiplier (:meth:`FastsumOperatorBank.mixture`), so every matvec of the
    multilayer adjacency/Laplacian costs exactly one fused pipeline — the
    same price as a single-layer graph.
    """
    bank = make_fastsum_bank(kernels, points, params)
    return _normalized_adjacency_from(bank.mixture(weights))


# ---------------------------------------------------------------------------
# Dense references (oracles / "direct method" baselines).
# ---------------------------------------------------------------------------

def dense_weight_matrix(kernel: Kernel, points: Array) -> Array:
    """W with zero diagonal (Eq. 2.3).  O(n^2) memory — tests/baselines only."""
    diff = points[:, None, :] - points[None, :, :]
    r = jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
    w = kernel.phi(r)
    return w - jnp.diag(jnp.diag(w))


def dense_normalized_adjacency(kernel: Kernel, points: Array) -> Array:
    w = dense_weight_matrix(kernel, points)
    deg = jnp.sum(w, axis=1)
    inv_sqrt = 1.0 / jnp.sqrt(deg)
    return inv_sqrt[:, None] * w * inv_sqrt[None, :]


@functools.partial(jax.jit, static_argnames=("kernel", "tile"))
def direct_matvec_tiled(kernel: Kernel, points: Array, x: Array,
                        tile: int = 2048) -> Array:
    """O(n^2) FLOPs, O(n*tile) memory direct matvec (the paper's baseline).

    Computes rows in tiles without materializing W; used by benchmarks for
    problem sizes where the dense matrix would not fit.  Jitted with the
    (frozen, hashable) kernel and tile size static, so repeated baseline
    timings measure compute rather than retracing.
    """
    n = points.shape[0]
    pad = (-n) % tile
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    n_tiles = pts.shape[0] // tile

    def row_block(i):
        rows = jax.lax.dynamic_slice_in_dim(pts, i * tile, tile, axis=0)
        diff = rows[:, None, :] - points[None, :, :]
        r = jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
        w = kernel.phi(r)
        # zero the true diagonal entries that fall inside this block
        row_ids = i * tile + jnp.arange(tile)
        col_ids = jnp.arange(n)
        w = jnp.where(row_ids[:, None] == col_ids[None, :], 0.0, w)
        # a float32 reference: TPU's default float32 matmul is one bf16 pass
        return jnp.matmul(w, x, precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(row_block, jnp.arange(n_tiles))
    return out.reshape(-1, *x.shape[1:])[:n]
