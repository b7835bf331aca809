"""The benchmark's own input generators, seeded.

Copies of the paper-experiment generators (``synthetic_image`` and
``spiral``), kept here so that the inputs a cell is measured on cannot move
with the program.  A configuration names its generator and sizes under
``data``; :func:`make_input` builds one input from a seed.  Every seed gives
the same shapes: only the noise and the order of the points change.
"""

from __future__ import annotations

import numpy as np


def spiral(n: int, n_classes: int = 5, h: float = 8.0, r: float = 2.0,
           noise: float = 0.1, seed: int = 0):
    """3-D conical spiral with ``n_classes`` arms (paper Fig. 2a).

    Returns ``(points (n, 3) float64, labels (n,) int32)``.
    """
    rng = np.random.default_rng(seed)
    per = n // n_classes
    pts, labs = [], []
    for c in range(n_classes):
        count = per + (1 if c < n % n_classes else 0)
        t = rng.uniform(0, 2 * np.pi, count)
        phi = 2 * np.pi * c / n_classes
        rad = r * (1 + t / np.pi)
        x = rad * np.cos(t + phi)
        y = rad * np.sin(t + phi)
        z = h * (t / np.pi - 1.0)
        pts.append(np.stack([x, y, z], -1) + rng.normal(0, noise, (count, 3)))
        labs.append(np.full(count, c, dtype=np.int32))
    points = np.concatenate(pts).astype(np.float64)
    labels = np.concatenate(labs)
    order = rng.permutation(points.shape[0])
    return points[order], labels[order]


def synthetic_image(height: int = 60, width: int = 90, noise: float = 8.0,
                    seed: int = 0):
    """Piecewise-constant RGB image (values 0..255) plus noise.

    Four regions: sky, ground, a disk ("sun") and a rectangle ("building"),
    a stand-in for the paper's 533x800 photograph (Fig. 5).  Returns
    ``(image (H, W, 3) float64, labels (H, W) int32)``.
    """
    rng = np.random.default_rng(seed)
    img = np.zeros((height, width, 3))
    lab = np.zeros((height, width), np.int32)
    img[:] = (70.0, 120.0, 200.0)  # sky

    horizon = int(height * 0.65)
    img[horizon:] = (60.0, 160.0, 70.0)  # ground
    lab[horizon:] = 1

    cy, cx, rad = int(height * 0.2), int(width * 0.75), max(3, height // 8)
    yy, xx = np.mgrid[0:height, 0:width]
    disk = (yy - cy) ** 2 + (xx - cx) ** 2 <= rad ** 2
    img[disk] = (250.0, 220.0, 60.0)  # sun
    lab[disk] = 2

    y0, y1 = int(height * 0.35), horizon
    x0, x1 = int(width * 0.15), int(width * 0.4)
    img[y0:y1, x0:x1] = (150.0, 60.0, 50.0)  # building
    lab[y0:y1, x0:x1] = 3

    img = np.clip(img + rng.normal(0, noise, img.shape), 0.0, 255.0)
    return img, lab


def make_input(data: dict, seed: int):
    """One input of a configuration's ``data`` entry: ``(points (n, d)
    float32, labels (n,) int32)``, the labels being the generator's own
    classes (an image's regions, a spiral's arms)."""
    kind = data["generator"]
    if kind == "synthetic_image":
        img, lab = synthetic_image(data["height"], data["width"], seed=seed)
        return img.reshape(-1, 3).astype(np.float32), lab.reshape(-1)
    if kind == "spiral":
        points, lab = spiral(data["n"], seed=seed)
        return points.astype(np.float32), lab
    raise ValueError(f"unknown data generator {kind!r}")


def make_points(data: dict, seed: int) -> np.ndarray:
    """The points of :func:`make_input`."""
    return make_input(data, seed)[0]


def job_seed(seed: int, job: int) -> int:
    """The seed of job ``job`` of a run: a 63-bit mix of both numbers."""
    return int(np.random.SeedSequence([seed, job]).generate_state(
        2, np.uint32).view(np.uint64)[0] >> np.uint64(1))
