"""The inputs of the kernel SSL cells: one fixed instance per cell.

A copy of the paper-experiment crescent generator, kept here so that the
inputs a cell is measured on cannot move with the program, and the sampler
of labelled nodes.  An instance is a point set, its classes and the few
nodes whose class is given, all made from one seed (the traffic's
``instance_seed``).  A job's input is that instance, its nodes in their own
order or in one drawn from the job's seed (:meth:`Instance.order`), which
the program sorts into Morton order: every job does the same solve.
"""

from __future__ import annotations

import numpy as np

from bench import data


def crescent_fullmoon(n: int, r1: float = 5.0, r2: float = 5.0,
                      r3: float = 8.0, seed: int = 0):
    """2-D crescent and full moon (paper Sec. 6.2.3), classes 1 to 3.

    Class 0: the disk of radius ``r1`` at the origin (the "full moon"),
    ``n // 4`` points.  Class 1: the lower half-annulus of radii ``r1 + r2``
    and ``r1 + r3`` centred at ``(0, r1)`` (the "crescent"), the rest.
    Returns ``(points (n, 2) float64, labels (n,) int32)``.
    """
    rng = np.random.default_rng(seed)
    n_moon = n // 4
    n_cres = n - n_moon

    ang = rng.uniform(0, 2 * np.pi, n_moon)
    rad = r1 * np.sqrt(rng.uniform(0, 1, n_moon))
    moon = np.stack([rad * np.cos(ang), rad * np.sin(ang)], -1)

    inner, outer = r1 + r2, r1 + r3
    ang_c = rng.uniform(np.pi, 2 * np.pi, n_cres)
    rad_c = np.sqrt(rng.uniform(inner ** 2, outer ** 2, n_cres))
    cres = np.stack([rad_c * np.cos(ang_c), rad_c * np.sin(ang_c) + r1], -1)

    points = np.concatenate([moon, cres]).astype(np.float64)
    labels = np.concatenate([np.zeros(n_moon, np.int32),
                             np.ones(n_cres, np.int32)])
    order = rng.permutation(n)
    return points[order], labels[order]


def make_input(spec: dict, seed: int):
    """``(points (n, d) float32, labels (n,) int32)`` of a configuration's
    ``data`` entry: the crescent here, every other generator in
    :mod:`bench.data`."""
    if spec["generator"] == "crescent_fullmoon":
        points, labels = crescent_fullmoon(spec["n"], spec["r1"], spec["r2"],
                                           spec["r3"], seed=seed)
        return points.astype(np.float32), labels
    return data.make_input(spec, seed)


def labelled_nodes(labels: np.ndarray, per_class: int, n_classes: int,
                   seed: int) -> np.ndarray:
    """``per_class`` nodes of every class, drawn without replacement:
    the sorted indices of the nodes whose class is given."""
    rng = np.random.default_rng([seed, 1])  # apart from the generator's
    chosen = [rng.choice(np.flatnonzero(labels == c), per_class,
                         replace=False) for c in range(n_classes)]
    return np.sort(np.concatenate(chosen))


class Instance:
    """One cell's fixed problem: ``points``, their ``classes``, and
    ``given`` (n,) int32, a node's class where it is labelled and -1
    elsewhere."""

    def __init__(self, spec: dict, instance_seed: int, per_class: int,
                 n_classes: int):
        self.points, self.classes = make_input(spec, instance_seed)
        self.labelled = labelled_nodes(self.classes, per_class, n_classes,
                                       instance_seed)
        self.given = np.full(self.classes.shape, -1, np.int32)
        self.given[self.labelled] = self.classes[self.labelled]

    @property
    def n(self) -> int:
        return self.points.shape[0]

    def order(self, job_seed: int) -> np.ndarray:
        """The node order of the job with seed ``job_seed``: node ``i`` of
        its input is node ``order[i]`` of the instance."""
        return np.random.default_rng(job_seed).permutation(self.n)
