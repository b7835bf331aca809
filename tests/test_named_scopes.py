"""The program's layer scopes (``repro.core.scopes``): each one is in the
compiled eigensolve program, on every path that runs the operator, and the
program compiled with them is the program compiled without them once the
op-name metadata is stripped."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FastsumParams, eigsh, fused_pipeline, make_fastsum,
                        make_kernel, make_normalized_adjacency, scopes)
from repro.graph.spectral import spectral_clustering
from repro.graph.ssl import kernel_ssl_cg

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
# the compiled program's text opens with tables of the source lines that
# made each operation (FileNames, FunctionNames, FileLocations,
# StackFrames), up to its first computation
_DEBUG_TABLES = re.compile(r"^FileNames\n.*?(?=^(?:%|ENTRY ))", re.S | re.M)

# (block size, k-means) of the two eigensolve jobs: Lanczos with k-means on
# its eigenvectors, and block Lanczos
JOBS = [(1, True), (4, False)]


def job_program(block: int, kmeans: bool):
    """The benchmark's eigensolve job at a tiny size: build the normalized
    adjacency of the points, ``eigsh`` on it, k-means on the eigenvectors."""
    kernel = make_kernel("gaussian", sigma=1.0)
    params = FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=0.125)
    k = 4

    def program(points, key):
        op = make_normalized_adjacency(kernel, points, params)
        res = eigsh(op.matvec, op.n, k, key=key, block_size=block,
                    dtype=op.inv_sqrt_deg.dtype)
        out = [op.degrees, res.eigenvalues, res.eigenvectors]
        if kmeans:
            out.append(spectral_clustering(
                op, k, key=key, eigenvectors=res.eigenvectors,
                eigenvalues=res.eigenvalues).assignments)
        return out

    return program


def compiled_text(fn, *args) -> str:
    jax.clear_caches()  # trace anew: a cached jaxpr keeps its op names
    return jax.jit(fn).lower(*args).compile().as_text()


def job_text(block: int, kmeans: bool) -> str:
    points = jax.random.normal(jax.random.PRNGKey(0), (96, 3))
    return compiled_text(job_program(block, kmeans), points,
                         jax.random.PRNGKey(1))


def strip_metadata(hlo_text: str) -> str:
    return _DEBUG_TABLES.sub("", _METADATA.sub("", hlo_text))


def found_scopes(hlo_text: str, where=lambda name: True) -> set:
    return {scopes.innermost(name) for name in _OP_NAME.findall(hlo_text)
            if where(name)} - {None}


@pytest.fixture(scope="module")
def scoped_jobs():
    return {job: job_text(*job) for job in JOBS}


@pytest.mark.parametrize("job", JOBS)
def test_every_scope_is_in_the_job_program(scoped_jobs, job):
    assert found_scopes(scoped_jobs[job]) == set(scopes.SCOPES)


@pytest.mark.parametrize("job", JOBS)
def test_scopes_change_nothing_but_op_name_metadata(scoped_jobs, job,
                                                   monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = job_text(*job)
    assert found_scopes(bare) == set()
    stripped = strip_metadata(bare)
    # every computation is kept, and nothing of the metadata
    assert stripped.count("\nENTRY ") == 1
    assert "op_name" not in stripped and "\nStackFrames\n" not in stripped
    assert strip_metadata(scoped_jobs[job]) == stripped


def ssl_text() -> str:
    """The benchmark's kernel SSL job at a tiny size: build the normalized
    adjacency of the points, then ``kernel_ssl_cg`` on four one-vs-rest
    columns; ``converged`` keeps the exit true-residual pass."""
    kernel = make_kernel("gaussian", sigma=1.0)
    params = FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=0.125)

    def program(points, f):
        op = make_normalized_adjacency(kernel, points, params)
        res = kernel_ssl_cg(op, f, 1e3, tol=1e-4, maxiter=50)
        return op.degrees, res.u, res.converged

    points = jax.random.normal(jax.random.PRNGKey(0), (96, 3))
    f = jnp.zeros((96, 4)).at[jnp.arange(8), jnp.arange(8) % 4].set(1.0)
    return compiled_text(program, points, f)


def test_cg_runs_in_the_krylov_scope(monkeypatch):
    """The CG recurrence and its exit true-residual pass run inside
    ``krylov``, the operator's scopes nested in it as under Lanczos; with
    the metadata stripped the program is the one without scopes."""
    text = ssl_text()
    assert found_scopes(text) == set(scopes.SCOPES) - {scopes.KRYLOV_ORTH}
    names = _OP_NAME.findall(text)
    loop = [n for n in names if n.startswith("jit(program)/krylov/while/")]
    exit_pass = [n for n in names if n.startswith(
        "jit(program)/krylov/jit(fused_matvec_tilde)/")]
    for solve in (loop, exit_pass):
        assert {scopes.innermost(n) for n in solve} >= {
            scopes.SPREAD, scopes.FFT_MID, scopes.GATHER}
    # nothing of the solve runs outside the scope: every loop is the
    # build's or krylov's
    assert all(n.split("/")[1] in ("build", "krylov")
               for n in names if "/while" in n)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = ssl_text()
    assert found_scopes(bare) == set()
    assert strip_metadata(text) == strip_metadata(bare)


def _operator(n: int = 64, d: int = 2):
    points = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    return make_fastsum(make_kernel("gaussian", sigma=1.0), points,
                        FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=0.125))


def test_the_hooked_pipeline_carries_the_operator_scopes():
    """The distributed matvec's path: a spectral hook in place of the
    multiply's reduce bypasses the custom VJP."""
    fs = _operator()
    text = compiled_text(
        lambda x: fused_pipeline(fs.plan, fs.multiplier_half, fs.src_window,
                                 fs.tgt_window, x,
                                 spectral_reduce=lambda block: 2.0 * block),
        jnp.ones(fs.n_source))
    assert found_scopes(text) == {scopes.SPREAD, scopes.FFT_MID,
                                  scopes.GATHER}


def test_the_custom_vjp_backward_carries_the_operator_scopes():
    fs = _operator()

    def loss(x, multiplier):
        return jnp.sum(fused_pipeline(fs.plan, multiplier, fs.src_window,
                                      fs.tgt_window, x) ** 2)

    text = compiled_text(jax.grad(loss, argnums=(0, 1)),
                         jnp.ones(fs.n_source), fs.multiplier_half)
    backward = found_scopes(text, where=lambda name: "transpose(" in name)
    assert backward == {scopes.SPREAD, scopes.FFT_MID, scopes.GATHER}


@pytest.mark.parametrize("op_name,scope", [
    # the Pallas kernels' custom calls, in the block solver's loop
    ("jit(program)/krylov/while/body/closed_call/jit(fused_matvec_tilde)/"
     "spread/jit(window_spread)/pallas_call", "spread"),
    ("jit(program)/krylov/while/body/closed_call/jit(fused_matvec_tilde)/"
     "gather/jit(window_gather)/pallas_call", "gather"),
    # the XLA window path: the spread's scatter in its loop over node tiles,
    # the gather's own gather primitive
    ("jit(program)/build/jit(fused_matvec_tilde)/spread/while/body/"
     "closed_call/scatter-add", "spread"),
    ("jit(program)/krylov/while/body/closed_call/jit(fused_matvec_tilde)/"
     "gather/while/body/closed_call/gather", "gather"),
    ("jit(program)/krylov/jit(fused_matvec_tilde)/fft_mid/jit(fft)/fft",
     "fft_mid"),
    ("jit(program)/krylov/while/body/krylov_orth/dot_general",
     "krylov_orth"),
    ("jit(program)/krylov/while/body/krylov_orth/jit(qr)/geqrf",
     "krylov_orth"),
    ("jit(program)/build/jit(build_window_geometry)/sort", "build"),
    ("jit(program)/krylov/jit(eigh)/eigh", "krylov"),
    # a scope under a transformation: the custom VJP's backward pass
    ("jit(f)/transpose(jvp(spread))/scatter-add", "spread"),
    ("jit(f)/transpose(jvp(transpose(jvp(fft_mid))))/jit(fft)/fft",
     "fft_mid"),
    # the primitive named gather, or a jitted function, is no scope
    ("jit(program)/jit(kmeans)/gather", None),
    ("jit(program)/jit(fft)/fft", None),
    ("jit(f)/jvp()/mul", None),
    ("scatter-add", None),
    ("", None),
])
def test_innermost(op_name, scope):
    assert scopes.innermost(op_name) == scope


# --------------------------------------------------------------------------
# The ``node_order`` scope: a Krylov solve on an operator with a node order
# permutes its inputs in once and its results out once, outside the loop,
# and no application inside the loop permutes node values.

N_NODES = 96


def _eqns(jaxpr, in_loop=False, path=""):
    """Every equation of a jaxpr and of the jaxprs inside it:
    ``(eqn, inside a loop, name stack)``."""
    for eqn in jaxpr.eqns:
        here = path + "/" + str(eqn.source_info.name_stack)
        yield eqn, in_loop, here
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(
                        inner,
                        in_loop or eqn.primitive.name in ("while", "scan"),
                        here)


def node_permutations(fn, *args):
    """The row gathers of node vectors ((n,) or (n, C) by (n, 1) indices)
    and the index scatters over n nodes in ``fn``'s program, as
    ``(primitive, inside a loop, under node_order)``."""
    out = []
    for eqn, in_loop, stack in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        shapes = [getattr(v.aval, "shape", ()) for v in eqn.invars]
        name = eqn.primitive.name
        if name == "gather" and shapes[0][:1] == (N_NODES,) \
                and shapes[1] == (N_NODES, 1):
            out.append((name, in_loop, scopes.NODE_ORDER in stack))
        elif name == "scatter" and shapes[0] == (N_NODES,) \
                and shapes[2] == (N_NODES,):
            out.append((name, in_loop, scopes.NODE_ORDER in stack))
    return out


def _adjacency(points):
    return make_normalized_adjacency(
        make_kernel("gaussian", sigma=1.0), points,
        FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=0.125))


def _points():
    return jax.random.normal(jax.random.PRNGKey(0), (N_NODES, 3))


@pytest.mark.parametrize("block", [1, 4])
def test_eigsh_permutes_twice_outside_its_loop(block):
    """The start vector in and the Ritz vectors out, under ``node_order``;
    the build's inverse scatter and degrees out; nothing in the loop."""
    def program(points, key):
        op = _adjacency(points)
        return eigsh(op.matvec, op.n, 4, key=key, block_size=block,
                     dtype=op.inv_sqrt_deg.dtype).eigenvectors

    perms = node_permutations(program, _points(), jax.random.PRNGKey(1))
    assert not any(in_loop for _, in_loop, _ in perms), perms
    assert [p for p in perms if p[2]] == [("gather", False, True)] * 2
    # the build: the geometry's one inverse scatter, the degrees mapped back
    assert sorted(p for p in perms if not p[2]) == [
        ("gather", False, False), ("scatter", False, False)]


@pytest.mark.parametrize("solve", ["cg", "kernel_ssl_cg"])
def test_cg_permutes_twice_outside_its_loop(solve):
    """``b`` in and ``x`` out under ``node_order``, nothing in the loop:
    ``cg`` on an operator's bound ``matvec``, and ``kernel_ssl_cg``."""
    from repro.core import cg

    def program(points, f):
        op = _adjacency(points)
        if solve == "cg":
            return cg(op.matvec, f, tol=1e-6, maxiter=20).x
        return kernel_ssl_cg(op, f, 1e3, tol=1e-6, maxiter=20).u

    perms = node_permutations(program, _points(), jnp.ones(N_NODES))
    assert not any(in_loop for _, in_loop, _ in perms), perms
    assert [p for p in perms if p[2]] == [("gather", False, True)] * 2


def test_a_bare_callable_keeps_the_callers_order():
    """A bare callable has no node order: every application in the loop
    permutes in and out (two row gathers), and no ``node_order`` scope."""
    def program(points, key):
        op = _adjacency(points)
        return eigsh(lambda x: op.matvec(x), op.n, 4, key=key,
                     dtype=op.inv_sqrt_deg.dtype).eigenvectors

    perms = node_permutations(program, _points(), jax.random.PRNGKey(1))
    assert not any(under for _, _, under in perms)
    assert [p for p in perms if p[1]] == [("gather", True, False)] * 2


def test_a_direct_matvec_builds_no_inverse():
    """Outside a solve ``matvec`` keeps the caller's order: one gather in,
    one out, and no index scatter per call."""
    op = _adjacency(_points())
    perms = node_permutations(op.matvec, jnp.ones(N_NODES))
    assert perms == [("gather", False, False)] * 2


def test_only_square_operators_with_a_morton_geometry_have_a_node_order():
    from repro.core import make_fastsum_bank, node_order

    points = _points()
    kernel = make_kernel("gaussian", sigma=1.0)
    params = FastsumParams(n_bandwidth=16, m=2, p=2, eps_b=0.125)
    op = _adjacency(points)
    assert node_order.of(op.matvec) is not None
    assert node_order.of(op.fastsum.matvec) is not None
    assert node_order.of(op.laplacian_matvec) is None
    assert node_order.of(lambda x: op.matvec(x)) is None
    rect = make_fastsum(kernel, points, params, target_points=points[:10])
    assert rect.node_order() is None
    assert node_order.of(rect.matvec_tilde) is None
    bank = make_fastsum_bank([kernel], points, params)
    assert node_order.of(bank.matvec) is None
    order = node_order.of(op.matvec)
    x = jax.random.normal(jax.random.PRNGKey(3), (N_NODES, 2))
    # the order's application is the operator's, in Morton rows
    np.testing.assert_allclose(
        np.asarray(order.matvec(x[order.perm])[order.inv]),
        np.asarray(op.matvec(x)), rtol=0, atol=1e-12)
