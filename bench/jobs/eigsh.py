"""Job kind ``eigsh``: build the normalized adjacency of a new point set and
find its leading eigenpairs, optionally clustering them (spectral
clustering).

Traffic parameters: ``k`` eigenpairs, ``block_size`` (block Lanczos when
above 1), ``kmeans`` (cluster the eigenvectors' rows with
``spectral_clustering``'s k-means, as that entry does, and return the
labels).

One job is one call of one compiled program: ``make_normalized_adjacency``,
then ``eigsh`` on its ``matvec``, then (with ``kmeans``)
``spectral_clustering`` given those eigenpairs, which splits the job's key
and runs its k-means just as it does when it runs ``eigsh`` itself.  Job
``j`` of a run takes the input made from ``data.job_seed(seed, j)``; the
next job's input is made on the host while the device runs the current
one.  The check compares the build's degrees, every returned eigenpair
and, with ``kmeans``, the labels against the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, data
from bench.jobs_common import compile_program, window_backend
from bench.reference import DirectOperator, lanczos_eigsh, spectral_labels
from repro.core import FastsumParams, eigsh, make_kernel
from repro.core import make_normalized_adjacency
from repro.core.lanczos import eigsh_setup
from repro.graph.spectral import spectral_clustering


def input_key(job_seed: int):
    """The job's PRNG key, made on the host: the same as
    ``jax.random.PRNGKey(job_seed % 2**31)``, with no device work."""
    return np.array([0, job_seed % 2 ** 31], np.uint32)


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.data = config["data"]
        self.seed = seed
        self.sigma = float(config["sigma"])
        self.k = int(traffic["k"])
        self.kmeans = bool(traffic["kmeans"])
        kernel = make_kernel(config["kernel"], sigma=self.sigma)
        params = FastsumParams(**config["fastsum"])
        k, block, kmeans = self.k, int(traffic["block_size"]), self.kmeans

        def program(points, key):
            op = make_normalized_adjacency(kernel, points, params)
            key_eigs = jax.random.split(key)[0] if kmeans else key
            res = eigsh(op.matvec, op.n, k, key=key_eigs, block_size=block,
                        dtype=op.inv_sqrt_deg.dtype)
            out = {"degrees": op.degrees, "eigenvalues": res.eigenvalues,
                   "eigenvectors": res.eigenvectors,
                   "bounds": res.residual_bounds,
                   "num_matvecs": jnp.asarray(res.num_matvecs, jnp.int32)}
            if kmeans:
                out["assignments"] = spectral_clustering(
                    op, k, key=key, eigenvectors=res.eigenvectors,
                    eigenvalues=res.eigenvalues).assignments
            return out

        self._next = (0, self.inputs(0))
        self.compiled = compile_program(program, *self._next[1])
        self.window_backend = window_backend(self.compiled)
        n = self._next[1][0].shape[0]
        setup = eigsh_setup(n, k, block_size=block, dtype=jnp.float32)
        # columns of one Lanczos application, and the control's steps
        self.columns = setup.block_size if setup.num_blocks else 1
        self.steps = (setup.num_blocks * setup.block_size if setup.num_blocks
                      else setup.num_iters)

    def inputs(self, j: int):
        """Job ``j``'s points and key, on the device."""
        s = data.job_seed(self.seed, j)
        return (jax.device_put(data.make_points(self.data, s)),
                jax.device_put(input_key(s)))

    def run(self, j: int) -> dict:
        ready, args = self._next
        if ready != j:
            args = self.inputs(j)
        out = self.compiled(*args)
        self._next = (j + 1, self.inputs(j + 1))  # while the device works
        return jax.block_until_ready(out)

    def applications(self, record: dict) -> list:
        """Column counts of the job's operator applications, in order: the
        build's degree pass, then the solver's own count."""
        return [1] + [self.columns] * int(record["num_matvecs"])

    def failed(self, record: dict) -> bool:
        return not (bool(jnp.all(jnp.isfinite(record["eigenvalues"])))
                    and bool(jnp.all(jnp.isfinite(record["bounds"]))))

    def release(self, keep: int) -> None:
        """Drop the program and the prepared input."""
        self.compiled = None
        self._next = (None, None)

    def check(self, j: int, record: dict, names=None) -> dict:
        """The compared numbers of job ``j`` (those in ``names``, or all
        that the record allows)."""
        points, labels = data.make_input(self.data,
                                         data.job_seed(self.seed, j))
        ref = DirectOperator(points, self.sigma)
        av = []  # A v with the reference's A, made once when first needed

        def ref_av():
            if not av:
                av.append(ref.a(jnp.asarray(record["eigenvectors"],
                                            jnp.float32)))
            return av[0]

        numbers = {
            "degree_rel_err": lambda: compare.degree_rel_err(
                record["degrees"], ref),
            "eig_residual_excess": lambda: compare.eig_residual_excess(
                record["eigenvalues"], record["eigenvectors"],
                record["bounds"], ref_av()),
            "eigval_gap": lambda: compare.eigval_gap(
                record["eigenvalues"], record["eigenvectors"], ref_av()),
            "ritz_orthogonality": lambda: compare.orthogonality(
                record["eigenvectors"])}
        if "assignments" in record:
            numbers["label_mismatch"] = lambda: compare.label_mismatch(
                record["assignments"], labels, self.k)
        return {name: read() for name, read in numbers.items()
                if names is None or name in names}

    def control(self, j: int, record: dict) -> dict:
        """The reference in the program's place, in bfloat16: plain Lanczos
        on the bfloat16 direct operator for as many steps as the program's
        Krylov subspace has, the benchmark's own k-means on its vectors,
        read by the same numbers."""
        s = data.job_seed(self.seed, j)
        low = DirectOperator(data.make_points(self.data, s), self.sigma,
                             precision="bfloat16")
        vals, vecs, bounds = lanczos_eigsh(
            low.a, low.n, self.k, self.steps, jax.device_put(input_key(s)),
            jnp.bfloat16)
        out = {"degrees": low.degrees, "eigenvalues": vals,
               "eigenvectors": vecs, "bounds": bounds}
        if self.kmeans:
            out["assignments"] = spectral_labels(vecs, self.k, s)
        return self.check(j, out)
