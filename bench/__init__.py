"""On-chip benchmark of the APSP system: one harness, cells as data.

``BENCHMARK.json`` at the repository root names the cells; each cell's
configuration, traffic mix and per-layer metrics are files of their own
under this directory (see ``harness.py``).
"""
