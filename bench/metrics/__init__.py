"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``.

Each module's ``read(run)`` takes the traced run's context -- ``grids``
(the window's grids: ``wall_s``, the program's per-bucket ``buckets``
records, ``ticks``), ``trace`` (``bench.trace_reduce.reduce``'s result)
and ``chips`` -- and returns the metric, or ``None`` when it finds nothing
to read.
"""
