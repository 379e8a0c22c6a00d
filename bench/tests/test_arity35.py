"""The cell ``wiki.arity35`` on the CPU at rehearsal size, sound and
broken, and the readers of the fold's stages after the first."""

import dataclasses
import types

import numpy as np
import pytest

from bench import run, span_reduce
from bench.run import load_metric

CELL = "wiki.arity35"
NEW = ("late_search_ms.closed", "late_bounds_ms.closed", "late_live.closed")
FOLD = "jit(_fold_core)/seclud.fold"


def _trace(ops, modules, chips=1):
    """A reduced trace as ``span_reduce.load`` gives it, the same ops on
    every chip."""
    return {"devices": {f"/device:TPU:{i}": {"ops": ops, "modules": modules}
                        for i in range(chips)},
            "host": [["bench.window", 0, 10_000_000]]}


# Two folds of 4 stages: stage 1 10-30, stage 2 30-50 (bounds 30-34),
# stage 3 50-60 (bounds 50-52, nested ops inside), stage 4 60-62.
ONE_FOLD = [["fusion.1", 0, 10, f"{FOLD}/gather/gather"],
            ["while.1", 10, 20, f"{FOLD}/stage1/while"],
            ["fusion.9", 12, 2, f"{FOLD}/stage1/bounds/gather"],
            ["fusion.11", 30, 4, f"{FOLD}/stage2/bounds/gather"],
            ["while.2", 34, 16, f"{FOLD}/stage2/while"],
            ["fusion.13", 50, 2, f"{FOLD}/stage3/bounds/gather"],
            ["while.3", 52, 8, f"{FOLD}/stage3/while"],
            ["fusion.20", 53, 3, f"{FOLD}/stage3/while/body/gather"],
            ["fusion.15", 60, 1, f"{FOLD}/stage4/bounds/add"],
            ["while.4", 61, 1, f"{FOLD}/stage4/while"],
            ["fusion.5", 62, 8, f"{FOLD}/count/scatter-add"]]


def _two_folds(shift=1000):
    ops = ONE_FOLD + [[n, s + shift, d, scope] for n, s, d, scope in ONE_FOLD]
    return ops, [["jit__fold_core(1)", 0, 70], ["jit__fold_core(1)", shift, 70]]


def _rec():
    return types.SimpleNamespace(trace={}, window_batches=lambda: [])


@pytest.mark.parametrize("chips", [1, 4])
def test_late_readers_take_the_union_per_fold(monkeypatch, chips):
    ops, modules = _two_folds()
    monkeypatch.setattr(span_reduce, "load", lambda _d: _trace(ops, modules, chips))
    rec = _rec()
    # Stages 2-4: 30-62 each fold, 32 ns; their bounds 4 + 2 + 1 = 7 ns.
    assert load_metric("late_search_ms.closed")(rec) == pytest.approx(32e-6)
    assert load_metric("late_bounds_ms.closed")(rec) == pytest.approx(7e-6)
    # The stage scopes' union, stage 1 included, is what fold_search_ms reads.
    assert load_metric("fold_search_ms.closed")(rec) == pytest.approx(52e-6)


def test_late_readers_read_nothing_without_the_scopes(monkeypatch):
    """A two-term fold has one stage; a program without the bounds scope
    (the parent's) has no ``bounds`` op: each reader returns None."""
    one_stage = [op for op in ONE_FOLD if "stage1" in op[3] or "stage" not in op[3]]
    monkeypatch.setattr(span_reduce, "load",
                        lambda _d: _trace(one_stage, [["jit__fold_core(1)", 0, 70]]))
    assert load_metric("late_search_ms.closed")(_rec()) is None
    assert load_metric("late_bounds_ms.closed")(_rec()) is None
    no_bounds = [[n, s, d, scope.replace("/bounds", "")] for n, s, d, scope in ONE_FOLD]
    monkeypatch.setattr(span_reduce, "load",
                        lambda _d: _trace(no_bounds, [["jit__fold_core(1)", 0, 70]]))
    assert load_metric("late_search_ms.closed")(_rec()) == pytest.approx(32e-6)
    assert load_metric("late_bounds_ms.closed")(_rec()) is None
    rec = types.SimpleNamespace(trace=None, window_batches=lambda: [])
    assert load_metric("late_search_ms.closed")(rec) is None


def test_late_live_over_the_window_batches():
    read = load_metric("late_live.closed")
    infos = [{"late_cells": 100.0, "late_live": 40.0}, {"late_cells": 300.0, "late_live": 60.0},
             {"late_cells": 0.0, "late_live": 0.0}, {"cells": 5.0}]
    rec = types.SimpleNamespace(window_batches=lambda: [types.SimpleNamespace(info=i)
                                                        for i in infos])
    assert read(rec) == pytest.approx(100.0 * 100 / 400)
    for batches in ([{"cells": 5.0}], [{"late_cells": 0.0, "late_live": 0.0}]):
        rec = types.SimpleNamespace(window_batches=lambda b=batches: [
            types.SimpleNamespace(info=i) for i in b])
        assert read(rec) is None


def test_rehearsal_is_correct_and_reads_the_new_metrics(monkeypatch):
    """Through the harness's own path with ``--trace 1``: every answer
    matches the reference, the program's counters give ``late_live``,
    and the device-trace readers read a trace of the fold's scopes (the
    CPU's own trace holds no device operations, so one is planted)."""
    ops, modules = _two_folds()
    monkeypatch.setattr(span_reduce, "load", lambda _d: _trace(ops, modules))
    cell = run.find_cell(CELL, rehearse=True)
    assert cell.traffic["queries"]["arity"] == [3, 4, 5]
    out = run.run_cell(cell, 2**32 + 35, 2.0, trace=True, rehearse=True)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(NEW) <= set(out["metrics"])
    assert 0.0 < out["metrics"]["late_live.closed"]["value"] < 100.0


def _alter_one(_cq, counts):
    out = np.array(counts, copy=True)
    out[0] += 1
    return out


def test_an_altered_answer_is_wrong():
    cell = run.find_cell(CELL, rehearse=True)
    out = run.run_cell(cell, 35, 1.0, trace=False, rehearse=True, fault=_alter_one, grace_s=3.0)
    assert out["correct"] is False
    assert out["checks"]["wrong"]["value"] > 0


def test_control_is_not_correct():
    """``--control`` on a corpus past 65,536 documents: the reference at
    16-bit document ids, in the program's place, reads ``correct:
    false``."""
    base = run.find_cell(CELL, rehearse=True)
    cell = dataclasses.replace(base, config={**base.config, "n_docs": 70_000})
    out = run.run_cell(cell, 2**31 + 35, 1.0, trace=False, rehearse=True, control=True)
    assert out["correct"] is False
    assert out["checks"]["wrong"]["value"] > 0 and out["checks"]["unanswered"]["value"] == 0

