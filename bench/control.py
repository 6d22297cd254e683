"""Readings for the limits of ``bench/check.py``: the program's, over many
seeds, and the lower-precision control's, on the same clusters.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 1]

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up
once for all seeds, a short window, the comparison), and then the same
sampled clusters run on the reference computed in float32 and judged in
the program's place.  Every seed prints one JSON line: the program's
numbers (the lower readings) and the control's (the upper readings).  The
configurations state float64; the control is the nearest precision below,
reached from the benchmark's own files: ``bench/reference`` runs its
delivery waterfill and its payload and energy sums in float32.

The benchmark's own runs never run this.  It needs the chip, like them.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import grid as G  # noqa: E402
from bench.run import ROOT, devices_for, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    entry, config, traffic = G.load_cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    devices = devices_for(entry["chips"])
    if devices is None:
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            res = run(entry, config, traffic, seed, args.seconds, False,
                      devices, control=True)
        except Exception as e:  # a crash is the control's reading too
            traceback.print_exc()
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "crashed": repr(e)[:500]}), flush=True)
            continue
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "program": {"correct": res["correct"],
                                      "checks": res["checks"]},
                          "control": res.get("control"),
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
