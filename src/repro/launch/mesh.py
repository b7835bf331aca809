"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets its host
device count before first jax init, and smoke tests see the 1 real device.

Mesh geometry (TPU v5e target):
  * single pod:  (16, 16)  -> ("data", "model")   256 chips
  * multi-pod:   (2, 16, 16) -> ("pod", "data", "model")   512 chips

"data" (and "pod") carry batch + FSDP sharding; "model" carries
tensor/expert/sequence parallelism.  The "pod" axis crosses the
data-center interconnect, so collectives on it are the expensive ones —
the sharding rules put only DP gradient reduction there.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes, the kind the LM stack assumes.

    The train step and the model pin GSPMD's propagation with
    ``with_sharding_constraint`` (``repro.models.common.shard``), which
    only accepts Auto axes; ``jax.make_mesh`` makes Explicit ones.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU smoke / small real runs)."""
    n = jax.device_count()
    assert n % model_parallel == 0, (n, model_parallel)
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def mesh_chip_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
