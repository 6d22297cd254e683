"""Demand-trace generators for the paper's three experiments.

Every generator returns a plain ``t -> (cpu, mem)`` callable for the
per-object simulator, and additionally attaches a declarative ``spec``
(:class:`TraceSpec`) describing the trace as a -- possibly periodic -- step
function.  :class:`TraceBank` compiles a whole cluster's specs into padded
arrays so the vectorized engine evaluates every VM's demand at time ``t``
with one ``searchsorted``-style pass instead of a Python call per VM.

Generators that build a whole cluster at once produce a :class:`TraceTable`
of segments instead: the batched engine packs it straight into a bank
(:meth:`TraceBank.from_table`), and :class:`TableTraces` makes the per-VM
callables only for a caller that asks for them.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from typing import Callable, Optional, Sequence

import numpy as np

DemandTrace = Callable[[float], tuple[float, float]]  # t -> (cpu MHz, mem MB)


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """A (periodic) step function: value of the last segment with t0 <= t.

    ``period`` is ``None`` for aperiodic traces; otherwise the segments are
    defined on ``t mod period``.  Segment boundaries use the same
    ``t >= t0`` comparison as the callable form, so both representations
    agree exactly on tick times.
    """

    segments: tuple                     # ((t0, cpu_mhz, mem_mb), ...) sorted
    period: Optional[float] = None


def _with_spec(fn: DemandTrace, spec: TraceSpec) -> DemandTrace:
    fn.spec = spec
    return fn


def spec_trace(spec: TraceSpec) -> DemandTrace:
    """The canonical callable for a declarative spec: the value of the last
    segment with ``t0 <= t`` (on ``t mod period`` when periodic) -- exactly
    the semantics :class:`TraceBank` compiles, so the callable and array
    forms agree at every evaluation point."""
    segments, period = spec.segments, spec.period

    def trace(t: float) -> tuple[float, float]:
        if period is not None:
            t = t % period
        cpu, mem = segments[0][1], segments[0][2]
        for t0, c, m in segments:
            if t >= t0:
                cpu, mem = c, m
            else:
                break
        return cpu, mem
    return _with_spec(trace, spec)


def traces_from_table(names: Sequence[str], segs: np.ndarray,
                      counts: Optional[np.ndarray] = None,
                      periods: Optional[np.ndarray] = None
                      ) -> dict[str, DemandTrace]:
    """Bulk trace factory: one array pass instead of n factory calls.

    ``segs`` is ``(n, k, 3)`` float rows of ``(t0, cpu_mhz, mem_mb)``
    segments, ``counts`` the per-row number of valid segments (default
    ``k``), ``periods`` the per-row period with non-finite meaning
    aperiodic.  Returns ``{name: trace}`` where each trace carries the same
    :class:`TraceSpec` the scalar factories would have attached -- the sweep
    layer builds tens of thousands of VM traces per grid, and the per-call
    normalization in :func:`step_trace` dominated cell construction.
    """
    segs = np.asarray(segs, dtype=np.float64)
    n, k = segs.shape[0], segs.shape[1]
    seg_rows = segs.tolist()
    # Convert everything to plain Python up front: per-element ndarray
    # indexing and np scalar ops in the loop cost more than the loop body.
    cnt = ([k] * n if counts is None
           else np.asarray(counts, dtype=np.int64).tolist())
    if periods is None:
        per = [None] * n
    else:
        pa = np.asarray(periods, dtype=np.float64)
        per = [p if f else None
               for p, f in zip(pa.tolist(), np.isfinite(pa).tolist())]
    out: dict[str, DemandTrace] = {}
    for name, row, c, p in zip(names, seg_rows, cnt, per):
        if c != k:
            row = row[:c]
        out[name] = spec_trace(TraceSpec(
            segments=tuple(tuple(s) for s in row), period=p))
    return out


def _bank_period(periods) -> np.ndarray:
    """A bank's period column: ``inf`` where a trace is aperiodic."""
    p = np.asarray(periods, dtype=np.float64)
    return np.where(np.isfinite(p), p, np.inf)


@dataclasses.dataclass(frozen=True, eq=False)
class TraceTable:
    """A cluster's demand traces as the arrays :func:`traces_from_table`
    takes: ``segs`` ``(n, k, 3)`` rows of ``(t0, cpu_mhz, mem_mb)``, the
    first ``counts[i]`` (1 to ``k``) of row ``i`` valid, ``periods``
    non-finite where a row is aperiodic; row ``i`` traces ``vm_ids[i]``."""

    vm_ids: tuple
    segs: np.ndarray
    counts: np.ndarray
    periods: np.ndarray

    def traces(self) -> dict[str, DemandTrace]:
        return traces_from_table(self.vm_ids, self.segs, self.counts,
                                 self.periods)

    def bank(self) -> "TraceBank":
        return TraceBank.from_table(self.vm_ids, self.segs, self.counts,
                                    self.periods)

    def same_demand(self, other: "TraceTable") -> bool:
        """True when both tables trace the same VMs with the same segments
        and periods, so that they compile to the same bank: what is past a
        row's ``counts`` is ignored, and all non-finite periods are one."""
        if self is other:
            return True
        if (self.vm_ids != other.vm_ids
                or not np.array_equal(self.counts, other.counts)
                or not np.array_equal(_bank_period(self.periods),
                                      _bank_period(other.periods))):
            return False
        w = int(self.counts.max(initial=0))
        valid = np.arange(w)[None, :] < self.counts[:, None]
        return np.array_equal(self.segs[:, :w][valid],
                              other.segs[:, :w][valid])


class TableTraces(Mapping):
    """``{vm_id: trace}`` of a :class:`TraceTable`, made on first use.
    The batched engine reads the table's bank, so a batched sweep never
    makes the per-VM callables."""

    def __init__(self, table: TraceTable):
        self.table = table
        self._traces: Optional[dict[str, DemandTrace]] = None

    def _made(self) -> dict[str, DemandTrace]:
        if self._traces is None:
            self._traces = self.table.traces()
        return self._traces

    def __getitem__(self, vm_id: str) -> DemandTrace:
        return self._made()[vm_id]

    def __iter__(self):
        return iter(self._made())

    def __len__(self) -> int:
        return len(self.table.vm_ids)


def constant(cpu_mhz: float, mem_mb: float) -> DemandTrace:
    return _with_spec(lambda t: (cpu_mhz, mem_mb),
                      TraceSpec(segments=((0.0, cpu_mhz, mem_mb),)))


def step_trace(segments: list[tuple[float, float, float]]) -> DemandTrace:
    """``segments``: [(t_start, cpu_mhz, mem_mb), ...] sorted by t_start."""
    def trace(t: float) -> tuple[float, float]:
        cpu, mem = segments[0][1], segments[0][2]
        for t0, c, m in segments:
            if t >= t0:
                cpu, mem = c, m
            else:
                break
        return cpu, mem
    return _with_spec(trace, TraceSpec(segments=tuple(
        (float(t0), float(c), float(m)) for t0, c, m in segments)))


def burst(base_cpu: float, burst_cpu: float, mem_mb: float,
          t_start: float, t_end: float) -> DemandTrace:
    """Paper Sec. V-B: flat, spike in [t_start, t_end), flat again."""
    return step_trace([(0.0, base_cpu, mem_mb),
                       (t_start, burst_cpu, mem_mb),
                       (t_end, base_cpu, mem_mb)])


def prime_time(off_cpu: float, prime_cpu: float, off_mem: float,
               prime_mem: float, period_s: float = 86400.0,
               prime_start_frac: float = 0.0,
               prime_frac: float = 0.5) -> DemandTrace:
    """Paper Sec. V-D: trading VMs idle half the day, heavy the other half."""
    def trace(t: float) -> tuple[float, float]:
        phase = (t % period_s) / period_s
        in_prime = (prime_start_frac <= phase <
                    prime_start_frac + prime_frac)
        return ((prime_cpu, prime_mem) if in_prime else (off_cpu, off_mem))

    # Periodic step form on t mod period.
    t_on = prime_start_frac * period_s
    t_off = (prime_start_frac + prime_frac) * period_s
    prime_vals = (prime_cpu, prime_mem)
    off_vals = (off_cpu, off_mem)
    if prime_start_frac + prime_frac >= 1.0:
        # phase lives in [0, 1), so a window crossing 1.0 simply runs to the
        # period's end (the callable above never wraps it around).
        if prime_start_frac <= 0.0:
            segs = [(0.0, *prime_vals)]
        else:
            segs = [(0.0, *off_vals), (t_on, *prime_vals)]
    elif prime_start_frac <= 0.0:
        segs = [(0.0, *prime_vals), (t_off, *off_vals)]
    else:
        segs = [(0.0, *off_vals), (t_on, *prime_vals), (t_off, *off_vals)]
    return _with_spec(trace, TraceSpec(segments=tuple(segs), period=period_s))


class TraceBank:
    """Array-compiled demand traces for a whole cluster.

    Rows follow the ``vm_order`` given at construction.  Traces without a
    ``spec`` attribute (hand-written callables) fall back to per-VM Python
    evaluation, so the bank is always exhaustive over traced VMs.
    """

    def __init__(self, vm_order: Sequence[str]):
        self.vm_order = list(vm_order)
        self.rows = np.zeros(0, dtype=np.int64)       # traced, array-backed
        self.period = np.zeros(0)
        self.bps = np.zeros((0, 1))
        self.cpu_vals = np.zeros((0, 1))
        self.mem_vals = np.zeros((0, 1))
        self.fallback: list[tuple[int, DemandTrace]] = []

    @classmethod
    def from_traces(cls, traces: dict[str, DemandTrace],
                    vm_order: Sequence[str]) -> "TraceBank":
        bank = cls(vm_order)
        row_of = {vid: i for i, vid in enumerate(vm_order)}
        rows, specs = [], []
        for vm_id, trace in traces.items():
            if vm_id not in row_of:
                continue
            spec = getattr(trace, "spec", None)
            if spec is None:
                bank.fallback.append((row_of[vm_id], trace))
            else:
                rows.append(row_of[vm_id])
                specs.append(spec)
        if rows:
            # One flattened scatter over every (vm, segment) pair instead of
            # a per-spec Python loop: the bank packs whole sweep grids, and
            # host-side packing sat on the end-to-end critical path.
            n = len(rows)
            counts = np.fromiter((len(s.segments) for s in specs),
                                 dtype=np.int64, count=n)
            max_segs = int(counts.max())
            flat = np.asarray([seg for s in specs for seg in s.segments],
                              dtype=np.float64)         # (sum(counts), 3)
            r_idx = np.repeat(np.arange(n), counts)
            c_idx = (np.arange(flat.shape[0])
                     - np.repeat(np.cumsum(counts) - counts, counts))
            bps = np.full((n, max_segs), np.inf)
            cpu = np.zeros((n, max_segs))
            mem = np.zeros((n, max_segs))
            bps[r_idx, c_idx] = flat[:, 0]
            cpu[r_idx, c_idx] = flat[:, 1]
            mem[r_idx, c_idx] = flat[:, 2]
            # Padding repeats the last value so idx overshoot is benign.
            pad_src = np.minimum(np.arange(max_segs)[None, :],
                                 counts[:, None] - 1)
            take = np.arange(n)[:, None]
            cpu = cpu[take, pad_src]
            mem = mem[take, pad_src]
            period = np.fromiter(
                ((np.inf if s.period is None else s.period) for s in specs),
                dtype=np.float64, count=n)
            bank.rows = np.asarray(rows, dtype=np.int64)
            bank.period = period
            bank.bps = bps
            bank.cpu_vals = cpu
            bank.mem_vals = mem
        return bank

    @classmethod
    def from_table(cls, vm_order: Sequence[str], segs: np.ndarray,
                   counts: np.ndarray, periods: np.ndarray) -> "TraceBank":
        """The bank of :func:`traces_from_table`'s traces over ``vm_order``,
        the table's row order, built from the arrays with no per-VM
        object: bit for bit what :meth:`from_traces` gives for them."""
        bank = cls(vm_order)
        segs = np.asarray(segs, dtype=np.float64)
        counts = np.asarray(counts, dtype=np.int64)
        n = segs.shape[0]
        if n:
            cols = np.arange(int(counts.max()))[None, :]
            # Padding repeats the last valid segment's values, as in
            # ``from_traces``; its breakpoints are ``inf``.
            src = np.minimum(cols, counts[:, None] - 1)
            rows = np.arange(n)[:, None]
            bank.rows = np.arange(n, dtype=np.int64)
            bank.period = _bank_period(periods)
            bank.bps = np.where(cols < counts[:, None], segs[rows, src, 0],
                                np.inf)
            bank.cpu_vals = segs[rows, src, 1]
            bank.mem_vals = segs[rows, src, 2]
        return bank

    def eval(self, t: float) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(rows, cpu, mem) for every traced VM at time ``t``."""
        if self.rows.size:
            phase = np.mod(t, self.period)     # t mod inf == t
            idx = np.sum(self.bps <= phase[:, None], axis=1) - 1
            idx = np.clip(idx, 0, None)
            take = np.arange(self.rows.size)
            cpu = self.cpu_vals[take, idx]
            mem = self.mem_vals[take, idx]
        else:
            cpu = np.zeros(0)
            mem = np.zeros(0)
        rows = self.rows
        if self.fallback:
            fb_rows = np.array([r for r, _ in self.fallback], dtype=np.int64)
            fb = [fn(t) for _, fn in self.fallback]
            rows = np.concatenate([rows, fb_rows])
            cpu = np.concatenate([cpu, np.array([c for c, _ in fb])])
            mem = np.concatenate([mem, np.array([m for _, m in fb])])
        return rows, cpu, mem
