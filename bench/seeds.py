#!/usr/bin/env python3
"""Many seeds of one cell in one process: the readings ``correct``'s
limits are set from.

    python3 bench/seeds.py --workload wiki4.closed --seconds 10 \\
        --seeds 11 12 13 --control-seeds 21 22 23
    JAX_PLATFORMS=cpu python3 bench/seeds.py --workload wiki.closed \\
        --seconds 1 --seeds 1 2 --control-seeds 3 --rehearse

The cell's set-up (corpus, fit, index) is built once, then each seed is
one run of ``bench/run.py``'s own path (:func:`run.run_seed`): its
stream, prewarm, warm-up, a window of ``--seconds`` at the cell's load,
and every answer checked against the reference.  A control seed runs the
same with the reference at 16-bit document ids in the program's place
(``bench/run.py --control``).  Each seed prints one JSON line with its
``correct``, ``attempted`` and ``checks``; the last line sums them up.
A measurement of the cell is ``bench/run.py``, one process a run; this
only reads the comparison, where a cell's set-up is too long to pay
once a seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the configuration's rehearsal size")
    args = ap.parse_args(argv)
    cell = run.find_cell(args.workload, args.rehearse)
    try:
        dep = run.build(cell, args.rehearse)
    except run.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    run.log(f"seeds: set-up {time.perf_counter() - run.T_PROCESS:.3f} s")
    rows = []
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for seed in seeds:
            out = run.run_seed(dep, seed, args.seconds, trace=False, control=control)
            row = {"seed": seed, "control": control, "correct": out["correct"],
                   "attempted": out["attempted"], "qps": out["metrics"].get("qps", {}).get("value"),
                   "checks": out["checks"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {
        "workload": args.workload, "device": dict(dep.device),
        "program": {"seeds": sum(not r["control"] for r in rows),
                    "correct": sum(r["correct"] for r in rows if not r["control"]),
                    "wrong_max": max((r["checks"]["wrong"]["value"] for r in rows
                                      if not r["control"]), default=None)},
        "control": {"seeds": sum(r["control"] for r in rows),
                    "correct": sum(r["correct"] for r in rows if r["control"]),
                    "wrong_min": min((r["checks"]["wrong"]["value"] for r in rows
                                      if r["control"]), default=None)},
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
