"""From a profiler trace to the device's busy time, its idle gaps and its
busiest operations.

The traced run records the window under JAX's profiler.  Each chip's plane
(``/device:TPU:<n>``) holds one event per device operation on its
``XLA Ops`` line; the host planes hold the benchmark's own spans
(``bench.<label>``, written with ``jax.profiler.TraceAnnotation``), on the
same clock.  The reduction:

* busy: the union of the chip's operation intervals inside the window,
  averaged over the chips used;
* idle gaps: the holes in that union, each labelled by the innermost host
  span open at its midpoint (``setup``, ``grid``, ``build``, ``pack``,
  ``compile``, ``dispatch``, ``harvest``, ``compare``, or ``none``);
* device operations: self time per operation name (an operation's time
  less that of the operations nested in it, such as a ``while`` loop's
  body), under its XLA name.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile

#: Per-operation events of a chip's plane; the per-program line stands in
#: where a backend writes no per-operation line.
OPS_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIX = "bench."
#: Spans that enclose the whole window say nothing about a gap inside it.
_ENCLOSING = ("window",)
TOP = 10


@dataclasses.dataclass
class Trace:
    """Events in nanoseconds on one clock."""

    chips: list        # per chip: [(start, end, op name), ...]
    spans: list        # host spans: [(start, end, label), ...]
    window: tuple      # (start, end)


def merge(intervals) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> dict:
    """Nanoseconds per name that each event spends outside the events
    nested in it (``events`` as ``(start, end, name)``)."""
    out: dict[str, int] = {}
    stack: list[list] = []          # [end, name, self time]

    def close():
        end, name, own = stack.pop()
        out[name] = out.get(name, 0) + own

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close()
    return out


def op_name(text: str) -> str:
    """The XLA name of an operation from its trace text
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _label_at(spans, t) -> str:
    best = None
    for s, e, label in spans:
        if s <= t <= e and label not in _ENCLOSING:
            if best is None or s >= best[0]:
                best = (s, label)
    return best[1] if best else "none"


def reduce(trace: Trace) -> dict:
    """``busy_s`` (mean over chips), ``window_s``, the top ``device_ops``
    and the longest ``idle_gaps``, each as ``[name, seconds]`` lists."""
    lo, hi = trace.window
    busy = []
    gaps = []
    per_op: dict[str, int] = {}
    for events in trace.chips:
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in events
                   if e > lo and s < hi]
        union = merge((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in union))
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label_at(trace.spans, (s + e) // 2)))
        for n, ns in self_times(clipped).items():
            per_op[n] = per_op.get(n, 0) + ns
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[label, ns / 1e9] for ns, label in gaps[:TOP]],
    }


def from_xplane(path: str, n_chips: int) -> Trace:
    """Read a profiler ``.xplane.pb``: the first ``n_chips`` device planes'
    operations, the benchmark's host spans, and the window span."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    chips, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in OPS_LINES if n in lines), None)
            if name is not None:
                chips[plane.name] = [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     op_name(e.name)) for e in lines[name].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      e.name[len(SPAN_PREFIX):]))
    windows = [(s, e) for s, e, label in spans if label == "window"]
    if not windows:
        raise ValueError(f"no bench.window span in {path}")
    ordered = sorted(chips, key=lambda n: int(n.rsplit(":", 1)[-1]))
    return Trace(chips=[chips[n] for n in ordered[:n_chips]], spans=spans,
                 window=windows[0])


class Tracer:
    """The profiler around the window, its trace in a temporary directory
    (removed once read)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1     # the benchmark's annotations
        opts.python_tracer_level = 0   # no per-call Python events
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self, n_chips: int) -> dict:
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise FileNotFoundError(f"no trace under {self.dir}")
            return reduce(from_xplane(files[0], n_chips))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
