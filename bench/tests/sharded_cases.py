"""The four-chip cell on four virtual CPU devices, sound and broken.

JAX fixes its device count when it starts, so ``test_harness.py`` runs
this in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/sharded_cases.py

It runs ``wiki4.closed`` at its rehearsal size through the harness's own
path (``run.build`` once, then ``run.run_seed`` per case), with each
fault the cell can have planted in the timed path, the prewarm against
the keys the program's sharded lowering gives, and the control on a
corpus past 65,536 documents; and prints one JSON object of the results.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import data, run  # noqa: E402

CELL = "wiki4.closed"
SEED = 2**33 + 7


def _alter_one(_cq, counts):
    out = np.array(counts, copy=True)
    out[0] += 1
    return out


def _drop_half(_cq, counts):
    return np.asarray(counts)[: len(counts) // 2]


def _brief(out):
    return {"correct": out["correct"], "count": out["device"]["count"],
            "checks": {k: v["value"] for k, v in out["checks"].items()}}


def _prewarm_keys(dep) -> dict:
    """The sharded prewarm's compiles on a cold process against the
    distinct shapes ``lower_plan_sharded`` gives the same windows."""
    from repro.core import device_engine as de
    from repro.core.batched_query import plan_segment_pairs

    batch = int(dep.cell.config["serve"]["max_batch"])
    n_stream = int(dep.cell.traffic["stream_queries"])
    order = data.serve_order(dep.cost, batch, n_stream // dep.pool.n_queries, run._seed(SEED, 3))
    stream = dep.pool.queries[order]
    windows = [(i, i + batch) for i in range(0, n_stream, batch)]
    sidx = dep.svc.sharded_index
    keys = set()
    for i, j in windows:
        low = de.lower_plan_sharded(
            plan_segment_pairs(sidx.host, de.as_queries(stream[i:j]), track_work=False), sidx)
        keys.add((low.n_cells, low.group_width, low.stage_levels, low.n_queries_pad))
    compiles = run._prewarm_served(dep.svc, stream, windows, dep.cell.chips)
    again = run._prewarm_served(dep.svc, stream, windows, dep.cell.chips)
    return {"keys": len(keys), "folds": len({k[1:] for k in keys}), "compiles": compiles,
            "compiles_again": again,
            "cached_folds": de._build_sharded_fold.cache_info().currsize}


def _no_psum(dep) -> dict:
    """The exchange between chips left out: each chip's counts are
    returned as they are, unsummed."""
    import jax

    from repro.core import device_engine as de

    psum = jax.lax.psum
    jax.lax.psum = lambda x, _axes: x
    de._build_sharded_fold.cache_clear()
    try:
        return _brief(run.run_seed(dep, SEED, 1.0, trace=False, grace_s=3.0))
    finally:
        jax.lax.psum = psum
        de._build_sharded_fold.cache_clear()


def main() -> int:
    cell = run.find_cell(CELL, rehearse=True)
    dep = run.build(cell, rehearse=True)
    out = {"prewarm": _prewarm_keys(dep)}
    for name, fault in (("sound", None), ("alter_one", _alter_one), ("drop_half", _drop_half)):
        out[name] = _brief(run.run_seed(dep, SEED, 1.0, trace=False, fault=fault, grace_s=3.0))
    out["no_psum"] = _no_psum(dep)
    big = dataclasses.replace(cell, config={**cell.config, "n_docs": 70_000})
    out["control"] = _brief(run.run_cell(big, 2**31 + 99, 1.0, trace=False, rehearse=True,
                                         control=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
