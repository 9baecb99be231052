"""kgbench: the on-chip benchmark of the MapSDI knowledge-graph engine.

``BENCHMARK.json`` at the checkout root names the cells; this package
holds everything that measures them: the harness (``harness.py``), the
seeded generators and plain references of each configuration's shape
(``shapes/``), the traffic loops (``loops/``), the traffic mixes
(``traffic/``), the configurations (``configs/``), one reader per
per-layer metric (``metrics/``), the profiler-trace reduction
(``devtrace.py``), the chip's peaks (``device.py``) and the tests.
"""
