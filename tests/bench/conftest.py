"""Tiny sizes of every configuration in ``BENCHMARK.json``, for the
benchmark's CPU tests.

``test_bench_harness.tiny_root`` cuts every configuration's data to the size
in that module's ``TINY_DATA``, which holds only the configurations it was
written with; ``_tiny_data_of_added_configs`` gives it the sizes of those
added since (``crescent_ssl``), for that module's tests alone.
``tiny_checkout`` makes the same checkout for the tests of the cells that
use them.
"""

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

# Only the data shrinks; kernel, setup, traffic and limits are the cells'
# own.  Each size is one at which the checks pass their limits: kernel
# SSL's true residual grows as the graph shrinks (at Fig. 5's setup 3.6e-3
# at 90x120 pixels and 7.6e-3 at 40x60, against the one-vs-rest test's
# limit 5e-3; the crescent 8.1e-4 at n = 3,000 against 2e-3, in float32 on
# the CPU).
TINY_DATA = {
    "fig5_segmentation": {"generator": "synthetic_image", "height": 90,
                          "width": 120},
    "spiral_setup2": {"generator": "spiral", "n": 1500},
    "crescent_ssl": {"generator": "crescent_fullmoon", "n": 3000, "r1": 5.0,
                     "r2": 5.0, "r3": 8.0},
}


@pytest.fixture(autouse=True)
def _tiny_data_of_added_configs(request, monkeypatch):
    if request.module.__name__.rpartition(".")[2] != "test_bench_harness":
        return
    tiny = request.module.TINY_DATA
    for name, data in TINY_DATA.items():
        if name not in tiny:  # its own sizes stay as they are
            monkeypatch.setitem(tiny, name, data)


@pytest.fixture
def tiny_checkout(tmp_path) -> Path:
    """A checkout-like directory: the benchmark's files with every
    configuration's data made tiny."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        path = root / entry["file"]
        config = json.loads(path.read_text())
        config["data"] = TINY_DATA[entry["name"]]
        config["n"] = (config["data"]["n"] if "n" in config["data"] else
                       config["data"]["height"] * config["data"]["width"])
        path.write_text(json.dumps(config))
    return root
