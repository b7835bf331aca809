"""Lanczos method for extremal eigenpairs (paper Section 4).

``lanczos(matvec, n, k_iters)`` builds the tridiagonalization

    A Q_k = Q_k T_k + beta_{k+1} q_{k+1} e_k^T

with *full reorthogonalization* (two-pass classical Gram-Schmidt per step —
the tall-skinny ``Q^T v`` / ``Q y`` products are MXU-friendly matmuls, see
DESIGN.md §3).  Eigenpairs of A come from the Ritz pairs of T_k.

``eigsh`` is the user-facing driver: runs Lanczos to a fixed subspace size
(or until the residual bound ``|beta_{k+1} w_k|`` converges), then extracts
the ``k`` algebraically largest (or smallest) Ritz pairs.

Everything is jit-compatible: the iteration is a ``lax.fori_loop`` over a
preallocated basis, the matvec is an arbitrary traceable callable (dense,
fast-summation, or Pallas-backed).

Every inner product and basis product runs at float32 precision
(``Precision.HIGHEST``): on TPU the default for a float32 matmul is one
bfloat16 pass, whose ~4e-3 relative rounding would cap orthogonality and
the Ritz pairs far above float32 accuracy.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import node_order, scopes

Array = jax.Array
Matvec = Callable[[Array], Array]

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


class LanczosResult(NamedTuple):
    alphas: Array  # (k,) diagonal of T
    betas: Array  # (k,) sub-diagonal; betas[i>=1] couples q_i to q_{i+1},
    #   betas[0] is never written and stays 0 (v0 is normalized before the
    #   iteration, so no ||r0|| is recorded anywhere)
    basis: Array  # (k, n) rows are the Lanczos vectors q_1..q_k
    residual_beta: Array  # beta_{k+1}
    breakdown_iter: Array | None = None  # first step with a non-finite
    #   recurrence (scalar int32); == num_iters when the run stayed clean.
    #   Steps at/after it never write into alphas/betas/basis.


class EigshHealth(NamedTuple):
    """Guard flags for an eigsh run (see :class:`repro.core.SolveHealth`).

    ``nonfinite`` — the Lanczos recurrence went non-finite (poisoned
    matvec, breakdown); the subspace was truncated at ``breakdown_iter``
    and the invalid tail of T was sentinel-masked out of the returned
    Ritz window, but ``residual_bounds`` are inf: do not trust the pairs.
    """

    nonfinite: Array  # bool scalar
    breakdown_iter: Array  # int32 scalar, == subspace size when clean


class LanczosLoopState(NamedTuple):
    """Checkpointable Lanczos iteration state (the ``fori_loop`` carry plus
    the step index).  ``lanczos_machine`` + segmented ``fori_loop`` runs
    reproduce :func:`lanczos` bit-identically: the body is a deterministic
    function of ``(i, carry)`` alone."""

    basis: Array      # (num_iters, n)
    alphas: Array     # (num_iters,)
    betas: Array      # (num_iters,)
    beta_next: Array  # scalar: coupling into the next step
    breakdown: Array  # scalar int32
    i: Array          # next step index (scalar int32)


def lanczos_machine(matvec: Matvec, v0: Array, num_iters: int,
                    *, reorthogonalize: bool = True):
    """Lanczos in resumable form: ``(state0, body, finish)``.

    ``body(i, carry)`` is a ``fori_loop`` body over the 5-tuple carry
    ``state[:-1]``; running steps ``[i0, i1)`` in any segmentation yields
    the same trajectory.  ``finish(state)`` wraps a :class:`LanczosResult`.
    """
    n = v0.shape[0]
    dtype = v0.dtype
    q = v0 / jnp.linalg.norm(v0)

    basis = jnp.zeros((num_iters, n), dtype=dtype).at[0].set(q)
    alphas = jnp.zeros((num_iters,), dtype=dtype)
    betas = jnp.zeros((num_iters,), dtype=dtype)

    def body(i, carry):
        basis, alphas, betas, beta_next, breakdown = carry
        alive = i < breakdown
        qi = basis[i]
        w = matvec(qi)
        alpha = jnp.vdot(qi, w, precision=jax.lax.Precision.HIGHEST
                         ).real.astype(dtype)
        w = w - alpha * qi - jnp.where(i > 0, betas[i], 0.0) * basis[jnp.maximum(i - 1, 0)]
        if reorthogonalize:
            # two-pass CGS against the filled part of the basis
            with jax.named_scope(scopes.KRYLOV_ORTH):
                mask = (jnp.arange(num_iters) <= i)[:, None].astype(dtype)
                for _ in range(2):
                    coeffs = _mm(basis * mask, w)
                    w = w - _mm((basis * mask).T, coeffs)
        beta = jnp.linalg.norm(w)
        # breakdown guard: a non-finite recurrence step (poisoned matvec)
        # truncates the factorization — nothing at/after it is ever
        # written, so NaNs cannot enter the carried basis or T entries
        ok = alive & jnp.isfinite(alpha) & jnp.isfinite(beta)
        breakdown = jnp.where(alive & ~ok, i, breakdown)
        alphas = alphas.at[i].set(jnp.where(ok, alpha, 0.0))
        write = jnp.logical_and(i + 1 < num_iters, ok)
        q_next = jnp.where(beta > 0, w / jnp.maximum(beta, jnp.finfo(dtype).tiny), 0.0)
        basis = jax.lax.cond(
            write,
            lambda b: b.at[i + 1].set(q_next),
            lambda b: b,
            basis,
        )
        betas = jax.lax.cond(
            write,
            lambda b: b.at[i + 1].set(beta),
            lambda b: b,
            betas,
        )
        return basis, alphas, betas, jnp.where(ok, beta, 0.0), breakdown

    state0 = LanczosLoopState(
        basis=basis, alphas=alphas, betas=betas,
        beta_next=jnp.zeros((), dtype),
        breakdown=jnp.asarray(num_iters, jnp.int32),
        i=jnp.zeros((), jnp.int32))

    def finish(state: LanczosLoopState) -> LanczosResult:
        return LanczosResult(alphas=state.alphas, betas=state.betas,
                             basis=state.basis,
                             residual_beta=state.beta_next,
                             breakdown_iter=state.breakdown)

    return state0, body, finish


def lanczos(matvec: Matvec, v0: Array, num_iters: int,
            *, reorthogonalize: bool = True) -> LanczosResult:
    """Run ``num_iters`` Lanczos steps from start vector ``v0``."""
    state0, body, finish = lanczos_machine(
        matvec, v0, num_iters, reorthogonalize=reorthogonalize)
    carry = jax.lax.fori_loop(0, num_iters, body, tuple(state0)[:-1])
    return finish(LanczosLoopState(*carry,
                                   i=jnp.asarray(num_iters, jnp.int32)))


class BlockLanczosResult(NamedTuple):
    t_matrix: Array  # (s, s) block-tridiagonal projection, s = blocks*b
    basis: Array  # (blocks, n, b) orthonormal block Lanczos basis
    residual_block: Array  # (b, b) B_{blocks+1} (R factor of the residual)
    breakdown_iter: Array | None = None  # first block step with a
    #   non-finite recurrence; == num_blocks when clean


class BlockLanczosLoopState(NamedTuple):
    """Checkpointable block-Lanczos iteration state (see
    :class:`LanczosLoopState`)."""

    basis: Array     # (num_blocks, n, b)
    a_blocks: Array  # (num_blocks, b, b)
    b_blocks: Array  # (num_blocks, b, b)
    resid: Array     # (b, b)
    breakdown: Array
    i: Array


def block_lanczos_machine(matvec: Matvec, v0: Array, num_blocks: int,
                          *, reorthogonalize: bool = True):
    """Block Lanczos in resumable ``(state0, body, finish)`` form."""
    n, b = v0.shape
    dtype = v0.dtype
    q0, _ = jnp.linalg.qr(v0)

    basis = jnp.zeros((num_blocks, n, b), dtype=dtype).at[0].set(q0)
    a_blocks = jnp.zeros((num_blocks, b, b), dtype=dtype)
    b_blocks = jnp.zeros((num_blocks, b, b), dtype=dtype)  # B_j couples j-1,j

    def body(j, carry):
        basis, a_blocks, b_blocks, resid, breakdown = carry
        qj = basis[j]
        w = matvec(qj)  # (n, b): one batched operator application
        a = _mm(qj.T, w)
        a = 0.5 * (a + a.T)  # exact symmetry of the diagonal block
        w = w - _mm(qj, a)
        w = w - jnp.where(j > 0, 1.0, 0.0) * _mm(
            basis[jnp.maximum(j - 1, 0)], b_blocks[j].T)
        with jax.named_scope(scopes.KRYLOV_ORTH):
            if reorthogonalize:
                # two-pass block CGS against the filled part of the basis
                mask = (jnp.arange(num_blocks) <= j)[:, None, None].astype(
                    dtype)
                flat = jnp.moveaxis(basis * mask, 1, 0).reshape(
                    n, num_blocks * b)
                for _ in range(2):
                    coeffs = _mm(flat.T, w)  # (blocks*b, b)
                    w = w - _mm(flat, coeffs)
            q_next, r_next = jnp.linalg.qr(w)
        # breakdown guard: truncate the factorization at the first block
        # step with a non-finite recurrence (see ``lanczos``)
        alive = j < breakdown
        ok = alive & jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(r_next))
        breakdown = jnp.where(alive & ~ok, j, breakdown)
        write = jnp.logical_and(j + 1 < num_blocks, ok)
        basis = jax.lax.cond(
            write, lambda bb: bb.at[j + 1].set(q_next), lambda bb: bb, basis)
        b_blocks = jax.lax.cond(
            write, lambda bb: bb.at[j + 1].set(r_next), lambda bb: bb,
            b_blocks)
        a_blocks = a_blocks.at[j].set(jnp.where(ok, a, 0.0))
        return (basis, a_blocks, b_blocks,
                jnp.where(ok, r_next, 0.0), breakdown)

    state0 = BlockLanczosLoopState(
        basis=basis, a_blocks=a_blocks, b_blocks=b_blocks,
        resid=jnp.zeros((b, b), dtype),
        breakdown=jnp.asarray(num_blocks, jnp.int32),
        i=jnp.zeros((), jnp.int32))

    def finish(state: BlockLanczosLoopState) -> BlockLanczosResult:
        a_blocks, b_blocks, breakdown = (state.a_blocks, state.b_blocks,
                                         state.breakdown)
        s = num_blocks * b
        t = jnp.zeros((s, s), dtype=dtype)
        for j in range(num_blocks):
            t = jax.lax.dynamic_update_slice(t, a_blocks[j], (j * b, j * b))
            if j > 0:
                # A Q_{j-1} = ... + Q_j R_j  =>  lower block (j, j-1) is R_j;
                # the coupling into the first dead block is zeroed so the
                # sentinel-masked tail stays decoupled from the valid head
                bj = jnp.where(j < breakdown, 1.0, 0.0) * b_blocks[j]
                t = jax.lax.dynamic_update_slice(t, bj.T,
                                                 ((j - 1) * b, j * b))
                t = jax.lax.dynamic_update_slice(t, bj, (j * b, (j - 1) * b))
        return BlockLanczosResult(t_matrix=t, basis=state.basis,
                                  residual_block=state.resid,
                                  breakdown_iter=breakdown)

    return state0, body, finish


def block_lanczos(matvec: Matvec, v0: Array, num_blocks: int,
                  *, reorthogonalize: bool = True) -> BlockLanczosResult:
    """Block Lanczos with block size ``b = v0.shape[1]`` (paper Section 4).

    Each step applies the operator to a whole (n, b) block — a single fused
    multi-RHS matvec that amortizes spread/gather — and orthogonalizes with
    tall-skinny matmuls (MXU-friendly: (s*b, n) @ (n, b)).  Builds

        A Q = Q T + Q_{next} B_{next} E_last^T

    with T block-tridiagonal (diagonal blocks A_j, off-diagonal B_j^T/B_j).
    """
    state0, body, finish = block_lanczos_machine(
        matvec, v0, num_blocks, reorthogonalize=reorthogonalize)
    carry = jax.lax.fori_loop(0, num_blocks, body, tuple(state0)[:-1])
    return finish(BlockLanczosLoopState(
        *carry, i=jnp.asarray(num_blocks, jnp.int32)))


class EigshResult(NamedTuple):
    eigenvalues: Array  # (k,) sorted descending (largest) / ascending (smallest)
    eigenvectors: Array  # (n, k)
    residual_bounds: Array  # (k,) |beta_{m+1} w_m| per Ritz pair
    num_iters: int
    num_matvecs: int = 0  # operator applications (block counts as one)
    health: EigshHealth | None = None


def _sentinel_mask(t: Array, valid: Array, which: str) -> Array:
    """Push the dead (breakdown-truncated, all-zero) tail of T out of the
    requested Ritz window: its diagonal gets a sentinel far on the *wrong*
    side of the spectrum, so argsort never selects a dead pair while shapes
    stay static."""
    amax = jnp.max(jnp.abs(t))
    sentinel = (amax + 1.0) * 1e3
    if which == "LA":
        sentinel = -sentinel
    return t + jnp.diag(jnp.where(valid, 0.0, sentinel))


class EigshSetup(NamedTuple):
    """Resolved eigsh run configuration.

    A deterministic function of the :func:`eigsh` call arguments — shared
    with the durable driver (:mod:`repro.runtime.durable`) so a resumed run
    rebuilds the *identical* iteration (same subspace size, same shrunken
    block, same PRNG-derived start vectors) and only the loop state needs
    checkpointing.  ``num_blocks == 0`` marks the single-vector path.
    """

    k: int
    which: str
    num_iters: int
    block_size: int
    num_blocks: int
    v0: Array


def eigsh_setup(n: int, k: int, *, num_iters: int | None = None,
                which: str = "LA", key: Array | None = None,
                dtype=jnp.float64, v0: Array | None = None,
                block_size: int = 1) -> EigshSetup:
    """Resolve the full eigsh configuration (see :class:`EigshSetup`)."""
    if which not in ("LA", "SA"):
        raise ValueError(which)
    if num_iters is None:
        num_iters = min(n, max(2 * k + 20, 30))
    num_iters = min(num_iters, n)
    if key is None:
        key = jax.random.PRNGKey(0)

    if block_size > 1:
        if v0 is not None:
            block_size = v0.shape[1]
        # Shrink oversized blocks: the subspace dimension
        # num_blocks * block_size must not exceed n (past that the residual
        # is rank-deficient and QR manufactures orthonormal-but-meaningless
        # directions) yet must still reach min(k, n) so the caller gets the
        # k pairs it asked for.
        block_size = min(block_size, max(n // 2, 1))
        need = min(k, n)
        while block_size > 1 and (n // block_size) * block_size < need:
            block_size -= 1
        if v0 is not None and v0.shape[1] > block_size:
            # the shrinking above reduced the block below the caller's v0
            # width (small n, non-dividing block): keep the leading columns
            v0 = v0[:, :block_size]
        num_blocks = max(min(-(-num_iters // block_size), n // block_size),
                         -(-need // block_size))
        if v0 is None:
            v0 = jax.random.normal(key, (n, block_size), dtype=dtype)
        return EigshSetup(k=k, which=which, num_iters=num_iters,
                          block_size=block_size, num_blocks=num_blocks,
                          v0=v0)

    if v0 is None:
        v0 = jax.random.normal(key, (n,), dtype=dtype)
    return EigshSetup(k=k, which=which, num_iters=num_iters, block_size=1,
                      num_blocks=0, v0=v0)


def ritz_from_block(res: BlockLanczosResult, setup: EigshSetup,
                    n: int) -> EigshResult:
    """Ritz extraction from a finished block-Lanczos factorization."""
    k, which = setup.k, setup.which
    num_blocks, block_size = setup.num_blocks, setup.block_size
    broke = res.breakdown_iter < num_blocks
    valid = jnp.repeat(jnp.arange(num_blocks) < res.breakdown_iter,
                       block_size)
    theta, w = jnp.linalg.eigh(_sentinel_mask(res.t_matrix, valid, which))
    basis_flat = jnp.moveaxis(res.basis, 1, 0).reshape(
        n, num_blocks * block_size)
    order = (jnp.argsort(-theta) if which == "LA"
             else jnp.argsort(theta))[:k]
    theta_k = theta[order]
    w_k = w[:, order]
    vecs = _mm(basis_flat, w_k)
    bottom = w_k[-block_size:, :]  # (b, k) last-block Ritz components
    bounds = jnp.linalg.norm(_mm(res.residual_block, bottom), axis=0)
    bounds = jnp.where(broke, jnp.inf, bounds)
    return EigshResult(eigenvalues=theta_k, eigenvectors=vecs,
                       residual_bounds=bounds,
                       num_iters=num_blocks * block_size,
                       num_matvecs=num_blocks,
                       health=EigshHealth(
                           nonfinite=broke,
                           breakdown_iter=res.breakdown_iter))


def ritz_from_lanczos(res: LanczosResult, setup: EigshSetup) -> EigshResult:
    """Ritz extraction from a finished single-vector Lanczos run."""
    k, which, num_iters = setup.k, setup.which, setup.num_iters
    broke = res.breakdown_iter < num_iters
    valid = jnp.arange(num_iters) < res.breakdown_iter
    # dead betas (coupling into the first dead step) are zeroed so the
    # sentinel tail stays decoupled from the valid leading block of T
    off = jnp.where(valid[1:], res.betas[1:], 0.0)
    # T_k is (num_iters x num_iters) tridiagonal
    t = jnp.diag(res.alphas) + jnp.diag(off, 1) + jnp.diag(off, -1)
    theta, w = jnp.linalg.eigh(_sentinel_mask(t, valid, which))  # ascending
    order = (jnp.argsort(-theta) if which == "LA"
             else jnp.argsort(theta))[:k]
    theta_k = theta[order]
    w_k = w[:, order]
    vecs = _mm(res.basis.T, w_k)  # (n, k)
    bounds = jnp.abs(res.residual_beta * w_k[-1, :])
    bounds = jnp.where(broke, jnp.inf, bounds)
    return EigshResult(eigenvalues=theta_k, eigenvectors=vecs,
                       residual_bounds=bounds, num_iters=num_iters,
                       num_matvecs=num_iters,
                       health=EigshHealth(nonfinite=broke,
                                          breakdown_iter=res.breakdown_iter))


def eigsh(matvec: Matvec, n: int, k: int, *, num_iters: int | None = None,
          which: str = "LA", key: Array | None = None,
          dtype=jnp.float64, v0: Array | None = None,
          block_size: int = 1) -> EigshResult:
    """Largest-/smallest-algebraic eigenpairs of a symmetric operator.

    ``which``: 'LA' (largest algebraic, the paper's use case for
    A = D^{-1/2} W D^{-1/2}) or 'SA' (smallest — e.g. for L_s directly).

    ``block_size > 1`` runs block Lanczos: ``num_iters`` still means the
    Krylov subspace dimension, but the operator is applied to (n, block)
    batches, so the number of matvec invocations drops by ~``block_size``
    (the fused fastsum engine executes a block in one spread/FFT/gather
    pass).  The matvec callable must accept (n, C) input in that case.

    Handed the bound ``matvec`` of an operator with a node order (a fastsum
    adjacency, :mod:`repro.core.node_order`), the recurrence runs in that
    order: the start vector is permuted in once and the Ritz vectors out
    once, and no application permutes.  The results are the same up to
    the order of floating-point sums.
    """
    with jax.named_scope(scopes.KRYLOV):
        setup = eigsh_setup(n, k, num_iters=num_iters, which=which, key=key,
                            dtype=dtype, v0=v0, block_size=block_size)
        order = node_order.of(matvec)
        if order is not None:
            matvec = order.matvec
            setup = setup._replace(v0=node_order.rows(order, setup.v0)[0])
        if setup.num_blocks:
            res = block_lanczos(matvec, setup.v0, setup.num_blocks)
            res = ritz_from_block(res, setup, n)
        else:
            res = ritz_from_lanczos(lanczos(matvec, setup.v0,
                                            setup.num_iters), setup)
        if order is None:
            return res
        return res._replace(
            eigenvectors=node_order.nodes(order, res.eigenvectors))


def eigsh_smallest_laplacian(adjacency_matvec: Matvec, n: int, k: int,
                             **kw) -> EigshResult:
    """Smallest eigenpairs of L_s = I - A via largest of A (paper Section 2).

    Returns eigenvalues of L_s (= 1 - theta) with the same eigenvectors.
    """
    res = eigsh(adjacency_matvec, n, k, which="LA", **kw)
    return EigshResult(eigenvalues=1.0 - res.eigenvalues,
                       eigenvectors=res.eigenvectors,
                       residual_bounds=res.residual_bounds,
                       num_iters=res.num_iters,
                       num_matvecs=res.num_matvecs,
                       health=res.health)
