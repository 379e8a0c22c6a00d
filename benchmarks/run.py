"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # quick sizes
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale-ish
  PYTHONPATH=src python -m benchmarks.run --only speedups
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: fast subset,
                                                     # writes BENCH_smoke.json

``--smoke`` exists so every CI run appends one comparable data point to the
perf trajectory: quick sizes, a fixed suite subset, and a JSON artifact
(``--out``) the workflow uploads.

A suite that raises prints an ``ERROR`` row and makes the run exit
non-zero in every mode.  Compiles go through the persistent cache of
:mod:`repro.compile_cache` (``<repo>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` is set).
"""

import argparse
import json
import sys
import time
from pathlib import Path

# Fast, deterministic-size suites: one clustering row, one index row, one
# kernel row, one serving-replay row set.  The heavy sweeps (scaling,
# datasets, roofline) stay out of the smoke path — CI budgets minutes,
# not hours.
SMOKE_SUITES = ("speedups", "compression", "kernels", "serving", "chaos")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="fast fixed subset; write a JSON artifact for CI")
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="artifact path for --smoke")
    args = ap.parse_args()
    quick = not args.full

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(Path(__file__).resolve().parents[1])

    from benchmarks import (
        bench_chaos,
        bench_cluster_time,
        bench_comparison_cost,
        bench_compression,
        bench_datasets,
        bench_kernels,
        bench_scaling,
        bench_serving,
        bench_speedups,
        bench_tc,
        roofline_table,
    )

    suites = {
        "datasets": bench_datasets,
        "speedups": bench_speedups,
        "scaling": bench_scaling,
        "cluster_time": bench_cluster_time,
        "tc": bench_tc,
        "compression": bench_compression,
        "comparison_cost": bench_comparison_cost,
        "kernels": bench_kernels,
        "serving": bench_serving,
        "chaos": bench_chaos,
        "roofline": roofline_table,
    }
    print("name,us_per_call,derived")
    rows = []
    errors = []
    t0 = time.time()
    for name, mod in suites.items():
        if args.only and args.only != name:
            continue
        if args.smoke and name not in SMOKE_SUITES:
            continue
        try:
            for r in mod.run(quick=quick):
                print(r, flush=True)
                rows.append(r)
        except Exception as e:
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            errors.append({"suite": name, "error": f"{type(e).__name__}: {e}"})
    total_s = time.time() - t0
    print(f"# total {total_s:.0f}s", file=sys.stderr)

    if args.smoke:
        parsed = []
        for r in rows:
            parts = str(r).split(",", 2)
            if len(parts) < 2:
                continue
            try:
                us = float(parts[1])
            except ValueError:
                continue
            parsed.append({
                "name": parts[0],
                "us_per_call": us,
                "derived": parts[2] if len(parts) > 2 else "",
            })
        with open(args.out, "w") as f:
            json.dump(
                {
                    "suites": list(SMOKE_SUITES),
                    "quick": quick,
                    "total_seconds": round(total_s, 2),
                    "rows": parsed,
                    "errors": errors,
                },
                f,
                indent=2,
            )
        print(f"# wrote {args.out} ({len(parsed)} rows)", file=sys.stderr)
    if errors:
        # A silent hole in the perf trajectory is worse than a red run:
        # fail loudly when any suite breaks, in every mode.
        sys.exit(1)


if __name__ == "__main__":
    main()
