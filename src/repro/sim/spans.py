"""Program-owned spans of the batched sweep's host work.

A span times one piece of host work in two ways at once.  It adds its
duration, in seconds, to a *record*: a plain ``{name: seconds}`` dict owned
by the work it belongs to and read after the call (``sweep.LAST_BATCH_INFO``
carries one per bucket).  And it opens a ``jax.profiler.TraceAnnotation``
named ``repro.<name>``, carrying the ids it is given (``sweep=``,
``bucket=``), so a profiler trace shows the span on its own clock beside
the device planes.  With no profiler recording the annotation costs next to
nothing, so spans are always on.

A record is never shared between threads: the sweep pipeline's workers
each record onto the record of their own bucket.  Spans of one name
accumulate, so work split over several places reads as one number.
"""

from __future__ import annotations

import contextlib
import itertools
import time

_SWEEP_IDS = itertools.count(1)


def next_sweep_id() -> int:
    """A fresh id for one sweep call; ids rise within a process."""
    return next(_SWEEP_IDS)


@contextlib.contextmanager
def span(name: str, record: dict, **ids):
    """Time the enclosed work into ``record[name]`` and annotate it in the
    profiler's trace as ``repro.<name>`` with ``ids``."""
    import jax
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}", **ids):
            yield
    finally:
        record[name] = record.get(name, 0.0) + time.perf_counter() - t0
