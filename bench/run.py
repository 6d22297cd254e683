"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``: the DRS deployment) and a
traffic mix (``bench/traffic/<name>.json``: the cluster families and
policies of a grid).  The entry the window drives is the program's public
``repro.sim.sweep.run_sweep(specs, engine="batch", n_devices=...)``: each
call is one grid of clusters, built, packed, compiled (from the cache),
dispatched, scanned on the device and harvested.

Set-up imports JAX, keeps its compile cache in the checkout (or where
``JAX_COMPILATION_CACHE_DIR`` says), and runs grid 0 once, which compiles
or loads every program the window will use.  The window then runs grids
1, 2, ... back to back until ``--seconds`` have passed; grid ``i`` draws
its cluster seeds from ``(--seed, i)``.  Its end-to-end metric is the
simulated host-ticks of every grid completed over the wall time from the
first grid's build to the last grid's harvest.  Nothing may compile inside
the window: the run counts compilations there and fails if there is one.

``--trace 1`` runs the same window under the profiler and reports the
per-layer metrics (one reader each, ``bench/metrics/<metric>.py``) and a
breakdown of device time and idle gaps.  After the window, a sample of the
window's clusters drawn from the seed is run again on the plain reference
(``bench/reference``), and the result decides ``correct`` (``bench/check.py``).

Every line but the last goes to standard error; the last lines there are
the numbers compared, each beside its limit.  The last line of standard
output is the result as one JSON object.  With no TPU, or fewer chips than
the cell asks for, the run prints why and exits 1 with no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
# The benchmark package and the program under test (``src/repro``).
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, grid as G, trace_reduce  # noqa: E402

#: The compile-request event JAX records for every backend compilation,
#: persistent-cache hits included.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's compile requests in this process from its creation."""

    def __init__(self):
        from jax import monitoring
        self.count = 0

        def listen(event, duration_s, **_):
            if event == COMPILE_EVENT:
                self.count += 1

        monitoring.register_event_duration_secs_listener(listen)


def devices_for(chips: int):
    """The devices of this run, or ``None`` (with the reason printed) when
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform!r}; "
            "the benchmark measures the chip only")
        return None
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX sees {len(devices)}")
        return None
    return devices


def _answers(cells: list[dict], res: dict) -> list:
    """Each cluster's answer from ``run_sweep``'s results, in grid order
    (``None`` where the program returned none)."""
    out = []
    for c in cells:
        r = res.get(c["name"], {}).get(c["policy"])
        out.append(None if r is None else
                   {k: getattr(r, k) for k in check.COUNTS + check.FLOATS})
    return out


@contextlib.contextmanager
def _layer_spans():
    """Host spans around the program's layers inside ``run_sweep``, written
    into the profiler's trace (traced runs only).  A layer whose entry the
    program no longer has is left without a span."""
    import jax
    from repro.sim import batch, sweep
    targets = [(sweep, "_build_batch_cells", "build"),
               (batch.BatchedSimulator, "__init__", "pack"),
               (batch.BatchedSimulator, "compile", "compile"),
               (batch.BatchedSimulator, "run_async", "dispatch"),
               (batch.PendingBatch, "result", "harvest")]
    saved = []
    for owner, attr, label in targets:
        fn = owner.__dict__.get(attr)
        if fn is None:
            continue

        def wrapped(*a, __fn=fn, __label=label, **kw):
            with jax.profiler.TraceAnnotation(f"bench.{__label}"):
                return __fn(*a, **kw)

        setattr(owner, attr, wrapped)
        saved.append((owner, attr, fn))
    try:
        yield
    finally:
        for owner, attr, fn in saved:
            setattr(owner, attr, fn)


def run(entry: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, devices, control: bool = False) -> dict:
    """One run of the cell on ``devices``; returns the result object.
    ``control`` judges the lower-precision control in the program's place
    (``bench/control.py``)."""
    import jax
    from repro.sim import sweep

    mismatches = G.program_mismatches(config)
    if mismatches:
        raise RuntimeError("the program does not run the configuration "
                           f"{config['name']}: {mismatches}")
    compiles = CompileCounter()
    chips = entry["chips"]
    policies = traffic["policies"]

    def run_grid(index: int) -> dict:
        t0 = time.perf_counter()
        cells = G.grid(config, traffic, seed, index)
        names = list(dict.fromkeys(c["name"] for c in cells))
        first = {c["name"]: c for c in cells}
        specs = [sweep.SweepSpec(**{k: v for k, v in first[n].items()
                                    if k != "policy"}) for n in names]
        with jax.profiler.TraceAnnotation("bench.grid"):
            res = sweep.run_sweep(specs, policies=policies, engine="batch",
                                  n_devices=config["n_devices"])
        t1 = time.perf_counter()
        return {"index": index, "cells": cells, "t0": t0, "t1": t1,
                "wall_s": t1 - t0,
                "buckets": [dict(b) for b in sweep.LAST_BATCH_INFO],
                "host_ticks": G.host_ticks(cells),
                "ticks": G.ticks(cells[0]),
                "answers": _answers(cells, res)}

    with jax.profiler.TraceAnnotation("bench.setup"):
        warm = run_grid(0)
    setup_s = time.perf_counter() - _T0
    log(f"set-up {setup_s!r} s; warm-up grid: {len(warm['cells'])} "
        f"clusters, buckets {warm['buckets']}")

    tracer = trace_reduce.Tracer() if trace else None
    spans = _layer_spans() if trace else contextlib.nullcontext()
    n_compiled = compiles.count
    grids = []
    with spans:
        if tracer:
            tracer.start()
        with jax.profiler.TraceAnnotation("bench.window"):
            t_start = time.perf_counter()
            while not grids or grids[-1]["t1"] - t_start < seconds:
                grids.append(run_grid(len(grids) + 1))
        if tracer:
            tracer.stop()
    window_s = grids[-1]["t1"] - t_start
    window_compiles = compiles.count - n_compiled

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:chips]]
    log(f"peak_bytes_in_use per device: {peaks}")
    log(f"grids completed in the window: {len(grids)} in {window_s!r} s")
    for g in grids:
        log(f"grid {g['index']}: wall {g['wall_s']!r} s, buckets "
            f"{g['buckets']}")
    log(f"compilations in the window: {window_compiles}")
    if window_compiles:
        raise RuntimeError(f"{window_compiles} compilation(s) inside the "
                           "measured window")

    host_ticks = sum(g["host_ticks"] for g in grids)
    result = {"correct": None, "attempted": 0, "failed": 0}
    if trace:
        reduced = tracer.reduce(n_chips=chips)
        context = {"grids": grids, "trace": reduced, "chips": chips}
        metrics = {}
        for m in per_layer_metrics(entry["name"]):
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(context)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {
            "host_ticks_per_s": {"value": host_ticks / window_s,
                                 "unit": "host-ticks/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["metrics"] = metrics
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": max(peaks)}
    if trace:
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}

    with jax.profiler.TraceAnnotation("bench.compare"):
        verdict = check.judge(grids, seed, config, control)
    result.update(correct=verdict["correct"],
                  attempted=verdict["attempted"], failed=verdict["failed"])
    if control:
        result["control"] = {"correct": verdict["control_correct"],
                             "checks": verdict["control_checks"]}
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def per_layer_metrics(workload: str) -> list[dict]:
    """The ``per_layer`` entries of ``BENCHMARK.json`` this cell reports."""
    bench = G.load_json(ROOT / "BENCHMARK.json")
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    entry, config, traffic = G.load_cell(args.workload)
    # The compile cache lives in the checkout unless the environment names
    # one; the program's own cache setup takes this directory.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    devices = devices_for(entry["chips"])
    if devices is None:
        return 1
    result = run(entry, config, traffic, args.seed, args.seconds,
                 bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
