"""Chip benchmark of the batched DRS sweep (``python3 bench/run.py``).

Cells, configurations, traffic mixes and per-layer metrics are data: see
``BENCHMARK.json`` at the checkout root and the files under ``configs/``,
``traffic/`` and ``metrics/``.
"""
