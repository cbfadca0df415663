"""The on-chip benchmark's harness: traffic generation, the serving window,
trace reduction, peaks, and the comparison that decides ``correct``.

Everything per configuration, traffic mix, cell or per-layer metric lives in
files of its own beside this package (``configs/``, ``traffic/``,
``cells/``, ``metrics/``), found by the names in ``BENCHMARK.json``.
"""
