"""Work of the window step, from shapes, and the chip's published peaks.

One formula for both window backends (the Pallas kernels and the XLA
scatter/gather loop), so that a change of backend is judged against the
same work.  It counts what one operator application's window step has to
do at the least:

* bytes that cross HBM: per node its ``d`` base indices (int32) and its
  ``d * taps`` window weights (float32), read once by the spread and once by
  the gather; the node values, read by the spread and written by the
  gather, ``C`` channels each; the padded grid ``(M + taps - 1)^d * C``
  float32, written once by the spread and read once by the gather;
* operations: per node and pass, the ``taps^d`` weight products
  (``d - 1`` multiplies each) and one multiply-add per tap and channel.

The least time is the larger of operations over the peak rate and bytes
over the peak bandwidth; at these sizes it is the bandwidth.
"""

from __future__ import annotations

import dataclasses
import math

# Published peaks of one chip, keyed by JAX's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class WindowWork:
    """Bytes and operations of one application's window step."""

    bytes: int
    ops: int

    def least_seconds(self, device_kind: str) -> float:
        return max(self.ops_seconds(device_kind),
                   self.bytes_seconds(device_kind))

    def ops_seconds(self, device_kind: str) -> float:
        return self.ops / peaks(device_kind)["flops_per_s"]

    def bytes_seconds(self, device_kind: str) -> float:
        return self.bytes / peaks(device_kind)["hbm_bytes_per_s"]

    def bound(self, device_kind: str) -> str:
        """Which peak bounds the least time: ``"memory"`` or ``"compute"``."""
        return ("memory" if self.bytes_seconds(device_kind)
                >= self.ops_seconds(device_kind) else "compute")


def grid_size(n_bandwidth: int, m: int, sigma_os: float = 2.0) -> int:
    """Oversampled grid points per dimension: even, at least
    ``sigma_os * N``, and at least ``N + 2m + 2``."""
    return max(int(math.ceil(sigma_os * n_bandwidth / 2) * 2),
               n_bandwidth + 2 * m + 2)


def window_work(n: int, d: int, grid_size: int, m: int,
                channels: int) -> WindowWork:
    """Spread plus gather of ``n`` nodes on a ``grid_size^d`` grid.

    ``m`` is the window cut-off (``taps = 2m + 1``); the padded grid has
    ``grid_size + taps - 1`` points per dimension.
    """
    taps = 2 * m + 1
    padded = grid_size + taps - 1
    geometry = n * d * 4 + n * d * taps * 4
    values = n * channels * 4
    grid = padded ** d * channels * 4
    nbytes = 2 * geometry + 2 * values + 2 * grid
    per_node = taps ** d * ((d - 1) + 2 * channels)
    return WindowWork(bytes=nbytes, ops=2 * n * per_node)
