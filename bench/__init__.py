"""On-chip benchmark for the serving engine: ``python3 bench/run.py``.

Each configuration (``configs/``), traffic mix (``traffic/``) and per-layer
metric (``metrics/``) is a file of its own, found by the name that
``BENCHMARK.json`` gives it; the modules here are the one general harness.
"""
