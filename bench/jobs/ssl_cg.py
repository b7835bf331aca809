"""Job kind ``ssl_cg``: kernel semi-supervised learning by CG (paper Sec.
6.2.2-6.2.3, Eq. (6.4)): label every node of a graph from a few labelled
ones by solving ``(I + beta L_s) u = f``.

Traffic parameters: ``instance_seed`` (the one problem every job solves),
``labelled_per_class`` and ``classes`` (the labelled nodes: that many of
each class), ``beta``, ``tol`` and ``maxiter`` (the solve's).

One job is one call of one compiled program: ``make_normalized_adjacency``,
the right-hand side from the partial labels (``training_matrix``: one
+-1 column for two classes, one-vs-rest columns for more),
``kernel_ssl_cg`` on it, its columns in lockstep, and the labels
(``predicted_labels``).  Every job solves the same instance
(:class:`bench.ssl_data.Instance`), its nodes in the order drawn from
``data.job_seed(seed, j)``, which the program sorts into Morton order, so
the seed moves only the float32 rounding.  Jobs run back to back, one at a
time: job ``j + 1``'s input is made on the host while the device runs job
``j``, and dispatched once job ``j``'s result is back.  The check maps the
job's results back to the instance's order and compares its degrees, the
true residual of its solution and its labels against the plain reference
(:mod:`bench.ssl_reference`).
"""

from __future__ import annotations

import jax
import numpy as np

from bench import compare, data, ssl_data, ssl_reference
from bench.jobs_common import compile_program, window_backend
from bench.reference import DirectOperator
from repro.core import FastsumParams, make_kernel
from repro.core import make_normalized_adjacency
from repro.graph import ssl


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.seed = seed
        self.sigma = float(config["sigma"])
        self.beta = float(traffic["beta"])
        self.tol = float(traffic["tol"])
        self.maxiter = int(traffic["maxiter"])
        classes = int(traffic["classes"])
        self.instance = ssl_data.Instance(
            config["data"], int(traffic["instance_seed"]),
            int(traffic["labelled_per_class"]), classes)
        self.f = ssl_reference.rhs(self.instance.given, classes)
        self.columns = self.f.shape[1]
        kernel = make_kernel(config["kernel"], sigma=self.sigma)
        params = FastsumParams(**config["fastsum"])
        beta, tol, maxiter = self.beta, self.tol, self.maxiter

        def program(points, given):
            op = make_normalized_adjacency(kernel, points, params)
            f = ssl.training_matrix(given, classes).astype(points.dtype)
            res = ssl.kernel_ssl_cg(op, f, beta, tol=tol, maxiter=maxiter)
            return {"degrees": op.degrees, "u": res.u,
                    "labels": ssl.predicted_labels(res.u),
                    "num_iters": res.num_iters, "converged": res.converged}

        self._next = (0, self.inputs(0))
        self.compiled = compile_program(program, *self._next[1])
        self.window_backend = window_backend(self.compiled)

    def order(self, j: int) -> np.ndarray:
        """Job ``j``'s node order: node ``i`` of its input is node
        ``order[i]`` of the instance."""
        return self.instance.order(data.job_seed(self.seed, j))

    def inputs(self, j: int):
        """Job ``j``'s points and partial labels, on the device."""
        order = self.order(j)
        return (jax.device_put(self.instance.points[order]),
                jax.device_put(self.instance.given[order]))

    def run(self, j: int) -> dict:
        ready, args = self._next
        if ready != j:
            args = self.inputs(j)
        out = self.compiled(*args)
        self._next = (j + 1, self.inputs(j + 1))  # while the device works
        return jax.block_until_ready(out)

    def applications(self, record: dict) -> list:
        """Column counts of the job's operator applications, in order: the
        build's degree pass, one per CG iteration (the columns run in
        lockstep, so as many as the longest column's ``num_iters``), and
        the exit true-residual pass."""
        iters = int(np.max(np.asarray(record["num_iters"])))
        return [1] + [self.columns] * (iters + 1)

    def failed(self, record: dict) -> bool:
        return not (np.all(np.isfinite(np.asarray(record["u"])))
                    and np.all(np.isfinite(np.asarray(record["degrees"]))))

    def release(self, keep: int) -> None:
        """Drop the program and the prepared input."""
        self.compiled = None
        self._next = (None, None)

    def check(self, j: int, record: dict, names=None) -> dict:
        """The compared numbers of job ``j`` (those in ``names``, or
        all)."""
        order = self.order(j)

        def back(x):  # job j's node order -> the instance's
            x = np.asarray(x)
            out = np.empty_like(x)
            out[order] = x
            return out

        ref = DirectOperator(self.instance.points, self.sigma)
        unlabelled = self.instance.given < 0
        numbers = {
            "degree_rel_err": lambda: compare.degree_rel_err(
                back(record["degrees"]), ref),
            "ssl_true_residual": lambda: ssl_reference.true_residual(
                self.f, back(record["u"]), self.beta, ref),
            "ssl_label_mismatch": lambda: ssl_reference.label_mismatch(
                back(record["labels"]), self.reference_labels(ref),
                unlabelled)}
        return {name: read() for name, read in numbers.items()
                if names is None or name in names}

    def reference_labels(self, ref: DirectOperator) -> np.ndarray:
        """The labels of the reference solve: plain float32 CG on the
        direct operator to the program's tolerance."""
        u, _ = ssl_reference.cg(ssl_reference.system(ref, self.beta), self.f,
                                tol=self.tol, steps=self.maxiter)
        return ssl_reference.labels(u)

    def control(self, j: int, record: dict) -> dict:
        """The reference in the program's place, in bfloat16: plain CG on
        the bfloat16 direct operator, vectors in bfloat16, for as many
        iterations as the program's longest column took, read by the same
        numbers."""
        low = DirectOperator(self.instance.points, self.sigma,
                             precision="bfloat16")
        steps = int(np.max(np.asarray(record["num_iters"])))
        u, _ = ssl_reference.cg(ssl_reference.system(low, self.beta), self.f,
                                steps=steps, dtype=jax.numpy.bfloat16)
        order = self.order(j)  # into job j's node order, as the program's
        return self.check(j, {"degrees": np.asarray(low.degrees)[order],
                              "u": np.asarray(u)[order],
                              "labels": ssl_reference.labels(u)[order]})
