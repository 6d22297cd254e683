"""The reduction from a profiler trace to busy time, idle gaps and the
busiest device operations."""

import glob
import json
import os
import pathlib

import numpy as np
import pytest

from bench import trace_reduce as TR


def _brute_busy(events, lo, hi):
    """Busy nanoseconds by marking every nanosecond (small traces only)."""
    busy = set()
    for s, e, _ in events:
        busy.update(range(max(s, lo), min(e, hi)))
    return len(busy)


def test_merge_joins_overlapping_and_touching_intervals():
    assert TR.merge([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7), (9, 9)]


def test_self_times_and_names():
    events = [(0, 100, "while.1"), (10, 30, "fusion.2"), (40, 90, "while.3"),
              (50, 60, "fusion.2"), (100, 120, "copy.4")]
    assert TR.self_times(events) == {"while.1": 30, "fusion.2": 30,
                                     "while.3": 40, "copy.4": 20}
    assert TR.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"


def test_reduce_small_trace():
    # Two chips; spans: the window, a grid, and inside it build then
    # dispatch; a harvest after.
    chip0 = [(100, 300, "while.1"), (250, 400, "fusion.2"),
             (700, 900, "while.1"), (950, 1000, "copy.3")]
    chip1 = [(0, 150, "while.1"), (600, 1100, "while.1")]
    spans = [(50, 1050, "window"), (50, 800, "grid"), (60, 420, "build"),
             (420, 690, "dispatch"), (800, 1040, "harvest")]
    trace = TR.Trace(chips=[chip0, chip1], spans=spans, window=(50, 1050))
    out = TR.reduce(trace)
    busy0 = _brute_busy(chip0, 50, 1050)
    busy1 = _brute_busy(chip1, 50, 1050)
    assert (busy0, busy1) == (550, 550)
    assert out["busy_s"] == pytest.approx((busy0 + busy1) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(1000 / 1e9)
    # Self time: fusion.2 (250..400) is nested in while.1 (100..300) only
    # in part; its 50 ns inside count for fusion.2, not for while.1.
    assert out["device_ops"][0] == ["while.1", pytest.approx(900 / 1e9)]
    # chip1's gap 150..600 (450 ns) is the longest: its midpoint 375 lies
    # in build; chip0's 400..700 (300 ns) has its midpoint 550 in dispatch.
    assert out["idle_gaps"][:2] == [["build", pytest.approx(450e-9)],
                                    ["dispatch", pytest.approx(300e-9)]]
    labels = {g[0] for g in out["idle_gaps"]}
    assert "window" not in labels


def test_reduce_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    tracer = TR.Tracer()
    tracer.dir = str(tmp_path)
    tracer.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.grid"):
            jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((32, 32))) \
                .block_until_ready()
    tracer.stop()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    trace = TR.from_xplane(path, n_chips=1)
    labels = {label for _, _, label in trace.spans}
    assert {"window", "grid"} <= labels
    lo, hi = trace.window
    assert hi > lo
    # The CPU backend writes no device plane: nothing is busy, and the
    # whole window is one gap inside the grid span.
    assert trace.chips == []
    assert TR.reduce(trace)["busy_s"] == 0.0


def test_reduce_recorded_tpu_slice():
    """6 ms of a cap-only grid's scan, recorded on a TPU v5e: the scan's
    ``while`` loop, a DRS invocation's ``conditional`` and the fusions
    nested in them."""
    data = json.loads((pathlib.Path(__file__).parent / "data"
                       / "tpu_ops_slice.json").read_text())
    lo, hi = data["window"]
    ops = [tuple(op) for op in data["ops"]]
    trace = TR.Trace(chips=[ops], spans=[(lo, hi, "window")],
                     window=(lo, hi))
    out = TR.reduce(trace)
    mask = np.zeros(hi - lo, dtype=bool)
    for s, e, _ in ops:
        mask[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    assert out["busy_s"] == pytest.approx(mask.sum() / 1e9, abs=1e-12)
    clipped = [(max(s, lo), min(e, hi), n) for s, e, n in ops]
    # Properly nested operations: self times add up to the busy time.
    assert sum(TR.self_times(clipped).values()) == mask.sum()
    names = [n for n, _ in out["device_ops"]]
    assert len(names) == TR.TOP and "while.49" in {n for *_, n in ops}
    assert all(s > 0 for _, s in out["device_ops"])
