"""From the profiler's trace to the numbers the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain lists (so a small recorded
trace can be kept as JSON for the self-tests), and ``reduce`` turns those
into busy time, the fold's device time, the top device operations and
the longest idle gaps, each gap named by the innermost host span that
was open at its middle.  All times in the reduced form are nanoseconds on
the trace's own clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List

# Device operations of the fused fold run inside the program's jitted
# ``_fold_core``; its module carries that name.  The sharded fold is the
# jit of a ``shard_map`` over the program's ``body`` closure around
# ``_fold_core`` (``_build_sharded_fold``): its module is ``jit_body``.
FOLD_MODULE = "_fold_core"
SHARDED_FOLD_MODULE = "jit_body"
WINDOW_SPAN = "bench.window"


def is_fold(module: str) -> bool:
    """Whether a device module event (``jit__fold_core(<id>)``) is a run
    of the fold, on one chip or sharded."""
    return FOLD_MODULE in module or module.split("(", 1)[0] == SHARDED_FOLD_MODULE


def load(trace_dir: str) -> Dict[str, object]:
    """The trace under ``trace_dir`` as ``{"devices": {plane: {"ops": [...],
    "modules": [...]}}, "host": [...]}``, each event ``[name, start, dur]``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[len("/device:TPU:"):].isdigit():
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    d[key].extend([_short(e.name), e.start_ns, e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns] for e in line.events)
    return {"devices": devices, "host": host}


def _short(name: str) -> str:
    """An operation's HLO instruction name, without its text: ``%while.3``."""
    return name.split(" = ", 1)[0]


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def window(trace: dict):
    """``(start, end)`` of the benchmark's window span on the trace clock,
    or None where the trace lacks it."""
    spans = [(s, s + d) for name, s, d in trace["host"] if name == WINDOW_SPAN]
    return (min(a for a, _ in spans), max(b for _, b in spans)) if spans else None


def reduce(trace: dict, top: int = 10) -> Dict[str, object]:
    """Busy and fold device time per chip (averaged over the chips that
    ran anything), the device operations that took the most time, and
    the longest idle gaps inside the window."""
    win = window(trace)
    devs = [d for d in trace["devices"].values() if d["ops"] or d["modules"]]
    busy, fold_s, fold_n = [], [], []
    by_op: Dict[str, float] = {}
    gaps: List[tuple] = []
    for d in devs:
        events = d["ops"] or d["modules"]
        iv = [(s, s + du) for _n, s, du in events]
        if win is not None:
            iv = [(max(a, win[0]), min(b, win[1])) for a, b in iv if b > win[0] and a < win[1]]
        merged = _union(iv)
        busy.append(sum(b - a for a, b in merged))
        for name, _s, du in d["ops"]:
            by_op[name] = by_op.get(name, 0.0) + du
        folds = [du for name, _s, du in d["modules"] if is_fold(name)]
        fold_s.append(sum(folds))
        fold_n.append(len(folds))
        edges = [win[0]] if win is not None else []
        edges += [x for ab in merged for x in ab]
        if win is not None:
            edges.append(win[1])
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(devs), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = sorted(trace["host"], key=lambda e: e[2])  # innermost (shortest) first
    named = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        name = next((h[0] for h in host if h[1] <= mid <= h[1] + h[2] and h[0] != WINDOW_SPAN),
                    "no host span")
        named.append([name, (b - a) * 1e-9])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "n_devices": len(devs),
        "busy_s": sum(busy) * 1e-9 / n,
        "window_s": (win[1] - win[0]) * 1e-9 if win is not None else None,
        "fold_device_s": sum(fold_s) * 1e-9 / n,
        "fold_modules": sum(fold_n) / n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in ops],
        "idle_gaps": named,
    }
