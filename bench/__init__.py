"""The on-chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

See :mod:`bench.harness` for how a cell's files are found by name.
"""
