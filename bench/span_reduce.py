"""The program's own spans and scopes in the profiler's trace.

The program marks its serving path with host spans named ``seclud.*``
(``seclud.batch`` with ``seclud.seal``, ``seclud.plan``,
``seclud.lower``, ``seclud.upload``, ``seclud.dispatch``,
``seclud.readback`` and ``seclud.reply`` inside it) and the fold's device
operations with named scopes (``seclud.fold/stage<s>`` and others).
The trace keeps an operation's scope path in the ``tf_op`` stat of the
event's metadata (``jit(_fold_core)/seclud.fold/stage1/while/body/...``;
read by hand on a one-chip v5e trace), which ``jax.profiler.ProfileData``
does not expose: ``_op_scopes`` reads it from the ``.xplane.pb`` itself
with a minimal schema of the fields it needs.

``load`` reads the trace directory once, keeping each device operation's
scope and the host's ``seclud.*`` and window spans; ``reduce`` computes
the device's idle intervals inside the window as
``bench/trace_reduce.py`` does, gives each idle instant to the innermost
``seclud.*`` span open at that instant, and takes the union of the
device intervals of the fold's stage operations.  ``for_record`` does
both once per run for the metric readers.  A trace of a program without
these spans or scopes reduces to nothing to read: the readers then
return None.  Times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import glob
import heapq
import os
from pathlib import Path
from typing import Dict, List, Optional

from bench.trace_reduce import WINDOW_SPAN, _short, _union, is_fold, window

# Where ``bench/run.py`` writes the trace of a ``--trace 1`` run.
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"
PREFIX = "seclud."
SCOPE_STAT = "tf_op"  # the op metadata stat that holds its scope path
STAGE_SCOPE = "seclud.fold/stage"
# The sharded fold's cross-chip sum: the all-reduce that ``jax.lax.psum``
# lowers to keeps the primitive's name as its HLO instruction name
# (``%psum.7`` in a four-chip v5e trace of ``wiki4.closed``), which does
# not depend on the scopes around it.
PSUM_OP = "%psum"
GROUPS = {
    "plan": ("seclud.plan",),
    "lower": ("seclud.lower",),
    "xfer": ("seclud.upload", "seclud.dispatch", "seclud.readback"),
    "loop": ("seclud.batch", "seclud.seal", "seclud.reply"),
}


def load(trace_dir: str) -> Dict[str, object]:
    """The trace under ``trace_dir`` as ``{"devices": {plane: {"ops":
    [[name, start, dur, scope]], "modules": [[name, start, dur]]}},
    "host": [[name, start, dur]]}``; ``host`` keeps only the window span
    and the ``seclud.*`` spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    scopes = _op_scopes(paths[-1])
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[len("/device:TPU:"):].isdigit():
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            scope = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d["ops"].extend([_short(e.name), e.start_ns, e.duration_ns,
                                     scope.get(e.name, "")] for e in line.events)
                elif line.name == "XLA Modules":
                    d["modules"].extend([e.name, e.start_ns, e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns] for e in line.events
                            if e.name.startswith(PREFIX) or e.name == WINDOW_SPAN)
    return {"devices": devices, "host": host}


def _xspace_class():
    """A message class for the part of the profiler's ``XSpace`` schema
    (``tsl/profiler/protobuf/xplane.proto``) that holds event metadata:
    planes, their event and stat metadata, and string stats.  Fields it
    leaves out (the events themselves) are skipped unparsed."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    schema = {
        "Stat": [("metadata_id", 1, F.TYPE_INT64, one), ("str_value", 5, F.TYPE_STRING, one),
                 ("ref_value", 7, F.TYPE_UINT64, one)],
        "EventMetadata": [("name", 2, F.TYPE_STRING, one), ("stats", 5, "Stat", many)],
        "StatMetadata": [("name", 2, F.TYPE_STRING, one)],
        "EventEntry": [("key", 1, F.TYPE_INT64, one), ("value", 2, "EventMetadata", one)],
        "StatEntry": [("key", 1, F.TYPE_INT64, one), ("value", 2, "StatMetadata", one)],
        "Plane": [("name", 2, F.TYPE_STRING, one), ("event_metadata", 4, "EventEntry", many),
                  ("stat_metadata", 5, "StatEntry", many)],
        "Space": [("planes", 1, "Plane", many)],
    }
    for msg, fields in schema.items():
        m = fdp.message_type.add(name=msg)
        for name, number, typ, label in fields:
            f = m.field.add(name=name, number=number, label=label)
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.Space"))


def _op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {op event name: scope path}}`` from the
    ``SCOPE_STAT`` stat of each event's metadata."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        key = next((k for k, v in stat_names.items() if v == SCOPE_STAT), None)
        if key is None:
            continue
        names = out.setdefault(plane.name, {})
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id == key:
                    names[e.value.name] = st.str_value or stat_names.get(st.ref_value, "")
    return out


def _innermost(spans: List[list]) -> List[tuple]:
    """``(start, end, name)`` pieces of time, each labelled with the
    innermost span open in it: of the spans open there, the one that
    started last (on one thread, spans nest)."""
    bounds = sorted({t for _n, s, d in spans for t in (s, s + d)})
    starts = sorted(((s, s + d, n) for n, s, d in spans), reverse=True)
    heap: List[tuple] = []  # (-start, end, name): the latest start on top
    out = []
    for a, b in zip(bounds, bounds[1:]):
        while starts and starts[-1][0] <= a:
            s, e, n = starts.pop()
            heapq.heappush(heap, (-s, e, n))
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2]))
    return out


def _overlap_by_name(gaps: List[tuple], pieces: List[tuple]) -> Dict[str, float]:
    """Total overlap of the sorted disjoint ``gaps`` with each label of
    the sorted disjoint ``pieces``."""
    out: Dict[str, float] = {}
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        a, b = gaps[i]
        pa, pb, name = pieces[j]
        lo, hi = max(a, pa), min(b, pb)
        if hi > lo:
            out[name] = out.get(name, 0.0) + (hi - lo)
        if b < pb:
            i += 1
        else:
            j += 1
    return out


def reduce(trace: dict) -> Dict[str, object]:
    """Per chip (averaged over the chips that ran anything): the idle
    nanoseconds inside the window under each innermost ``seclud.*`` span
    (``idle_ns``), the fold modules that start inside the window
    (``window_folds``), all fold modules (``fold_modules``) and the
    union of the device intervals of ops under a fold stage's scope
    (``search_ns``, over the whole trace, as the fold's device time), and
    likewise of the cross-chip sum's ops (``psum_ns``)."""
    win = window(trace)
    spans = [h for h in trace["host"] if h[0].startswith(PREFIX)]
    pieces = _innermost(spans)
    devs = [d for d in trace["devices"].values() if d["ops"] or d["modules"]]
    idle: Dict[str, float] = {}
    window_folds = fold_modules = search = psum = 0.0
    for d in devs:
        folds = [(s, du) for name, s, du in d["modules"] if is_fold(name)]
        fold_modules += len(folds)
        search += sum(b - a for a, b in _union(
            [(s, s + du) for _n, s, du, scope in d["ops"] if STAGE_SCOPE in scope]))
        psum += sum(b - a for a, b in _union(
            [(s, s + du) for name, s, du, _scope in d["ops"]
             if name.split(".", 1)[0] == PSUM_OP]))
        if win is None:
            continue
        window_folds += sum(win[0] <= s < win[1] for s, _du in folds)
        events = d["ops"] or d["modules"]
        iv = [(max(e[1], win[0]), min(e[1] + e[2], win[1])) for e in events
              if e[1] + e[2] > win[0] and e[1] < win[1]]
        edges = [win[0]] + [x for ab in _union(iv) for x in ab] + [win[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
                if edges[i + 1] > edges[i]]
        for name, ns in _overlap_by_name(gaps, pieces).items():
            idle[name] = idle.get(name, 0.0) + ns
    n = max(len(devs), 1)
    return {
        "n_spans": len(spans),
        "idle_ns": {k: v / n for k, v in idle.items()},
        "window_folds": window_folds / n,
        "fold_modules": fold_modules / n,
        "search_ns": search / n,
        "psum_ns": psum / n,
    }


_CACHE: Dict[str, object] = {"rec": None, "out": None}


def for_record(rec) -> Optional[Dict[str, object]]:
    """``reduce(load(TRACE_DIR))`` for the run ``rec`` describes, read
    once per run; None for a run without a trace."""
    if rec.trace is None:
        return None
    if _CACHE["rec"] is not rec:
        _CACHE["rec"], _CACHE["out"] = rec, reduce(load(str(TRACE_DIR)))
    return _CACHE["out"]


def idle_ms(rec, group: str) -> Optional[float]:
    """Device-idle ms per fold in the window under the spans of ``group``
    (a key of ``GROUPS``); None where the trace has no program spans."""
    t = for_record(rec)
    if not t or not t["n_spans"] or not t["window_folds"]:
        return None
    ns = sum(t["idle_ns"].get(name, 0.0) for name in GROUPS[group])
    return ns / t["window_folds"] * 1e-6
