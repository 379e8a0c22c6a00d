import types

import pytest

from bench import span_reduce


def _trace(host, ops, modules):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host}


def test_idle_goes_to_the_innermost_span():
    # Window 0-100.  Device busy 30-60 (one fold).  Host: a batch 5-95
    # holding seal 5-10, plan 10-20, lower 20-28, upload 28-29, dispatch
    # 29-31, readback 31-62 and reply 62-70.
    host = [["bench.window", 0, 100], ["bench.dispatch", 8, 60],
            ["seclud.batch", 5, 90], ["seclud.seal", 5, 5], ["seclud.plan", 10, 10],
            ["seclud.lower", 20, 8], ["seclud.upload", 28, 1], ["seclud.dispatch", 29, 2],
            ["seclud.readback", 31, 31], ["seclud.reply", 62, 8]]
    ops = [["while.1", 30, 30, "jit(_fold_core)/seclud.fold/stage1/while"]]
    modules = [["jit__fold_core(1)", 30, 30]]
    r = span_reduce.reduce(_trace(host, ops, modules))
    assert r["window_folds"] == 1 and r["fold_modules"] == 1 and r["n_spans"] == 8
    idle = r["idle_ns"]
    assert idle["seclud.plan"] == pytest.approx(10)
    assert idle["seclud.lower"] == pytest.approx(8)
    assert idle["seclud.upload"] == pytest.approx(1)
    assert idle["seclud.dispatch"] == pytest.approx(1)  # the device starts at 30
    assert idle["seclud.readback"] == pytest.approx(2)  # the device ends at 60
    assert idle["seclud.seal"] == pytest.approx(5) and idle["seclud.reply"] == pytest.approx(8)
    # The batch span is innermost only after the reply: 70-95.
    assert idle["seclud.batch"] == pytest.approx(25)
    # 0-5 and 95-100 lie under no program span: no key takes them.
    assert sum(idle.values()) == pytest.approx(70 - 10)


def test_union_of_nested_ops_under_a_stage_scope():
    ops = [["fusion.1", 0, 10, "jit(_fold_core)/seclud.fold/gather/gather"],
           ["while.1", 10, 50, "jit(_fold_core)/seclud.fold/stage1/while"],
           ["fusion.18", 12, 20, "jit(_fold_core)/seclud.fold/stage1/while/body/lt"],
           ["fusion.18", 50, 15, "jit(_fold_core)/seclud.fold/stage1/while/body/lt"],
           ["while.2", 70, 10, "jit(_fold_core)/seclud.fold/stage2/while"],
           ["fusion.5", 80, 5, "jit(_fold_core)/seclud.fold/count/scatter-add"]]
    modules = [["jit__fold_core(1)", 0, 85]]
    r = span_reduce.reduce(_trace([["bench.window", 0, 100]], ops, modules))
    # stage1: 10-60 (the nested ops add nothing, the op past 60 adds 5);
    # stage2: 70-80.
    assert r["search_ns"] == pytest.approx(50 + 5 + 10)
    assert r["fold_modules"] == 1


def test_gaps_outside_the_window_are_ignored():
    host = [["seclud.plan", -50, 40], ["seclud.lower", 90, 30], ["bench.window", 0, 100]]
    ops = [["a", -60, 70, ""], ["b", 50, 45, ""]]
    modules = [["jit__fold_core(1)", -60, 70], ["jit__fold_core(2)", 50, 45]]
    r = span_reduce.reduce(_trace(host, ops, modules))
    # Idle inside the window: 10-50 (no span) and 95-100 (lower); the
    # plan span's idle time before the window does not count.
    assert r["idle_ns"] == {"seclud.lower": pytest.approx(5)}
    assert r["window_folds"] == 1 and r["fold_modules"] == 2
    assert r["search_ns"] == 0


def test_a_trace_without_program_spans_reads_nothing(monkeypatch):
    """The readers return None, and do not raise, on a program without
    the spans and scopes (a trace with the benchmark's spans only)."""
    from bench.run import load_metric

    trace = _trace([["bench.window", 0, 100], ["bench.dispatch", 10, 50]],
                   [["while.1", 20, 30, ""]], [["jit__fold_core(1)", 20, 30]])
    monkeypatch.setattr(span_reduce, "load", lambda _d: trace)
    rec = types.SimpleNamespace(trace={}, window_batches=lambda: [
        types.SimpleNamespace(info={"t_plan_s": 0.1, "n_kernel_calls": 1.0})])
    for name in ("fold_search_ms", "idle_plan_ms", "idle_lower_ms", "idle_xfer_ms",
                 "idle_loop_ms", "dead_cells", "upload_mb"):
        assert load_metric(f"{name}.closed")(rec) is None, name


def test_idle_metrics_divide_by_the_window_folds(monkeypatch):
    from bench.run import load_metric

    host = [["bench.window", 0, 4_000_000], ["seclud.batch", 0, 4_000_000],
            ["seclud.lower", 0, 1_000_000], ["seclud.lower", 2_000_000, 1_000_000]]
    modules = [["jit__fold_core(1)", 1_000_000, 1_000_000],
               ["jit__fold_core(2)", 3_000_000, 1_000_000]]
    ops = [["while.1", s, d, "jit(_fold_core)/seclud.fold/stage1/while"] for _n, s, d in modules]
    monkeypatch.setattr(span_reduce, "load", lambda _d: _trace(host, ops, modules))
    info = {"cells": 80.0, "cells_true": 60.0, "upload_bytes": 2e6, "n_kernel_calls": 1.0}
    rec = types.SimpleNamespace(trace={}, window_batches=lambda: [
        types.SimpleNamespace(info=info), types.SimpleNamespace(info=dict(info, cells=120.0))])
    assert load_metric("idle_lower_ms.closed")(rec) == pytest.approx(1.0)
    assert load_metric("idle_plan_ms.closed")(rec) == 0.0
    assert load_metric("fold_search_ms.closed")(rec) == pytest.approx(1.0)
    assert load_metric("dead_cells.closed")(rec) == pytest.approx(100 * (20 + 60) / 200)
    assert load_metric("upload_mb.closed")(rec) == pytest.approx(2.0)


def test_op_scopes_read_from_the_event_metadata(tmp_path):
    """The scope path sits in the ``tf_op`` stat of an op's event
    metadata, as a string or as a reference to a stat metadata name."""
    space = span_reduce._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in [(1, "hlo_category"), (2, "tf_op"), (3, "jit(_fold_core)/seclud.fold/count")]:
        plane.stat_metadata.add(key=key).value.name = name
    ops = [("%fusion.18 = s32[8] fusion()", "jit(_fold_core)/seclud.fold/stage1/while/body", 0),
           ("%fusion.5 = s32[8] fusion()", "", 3), ("%while = s32[8] while()", None, 0)]
    for i, (name, scope, ref) in enumerate(ops):
        md = plane.event_metadata.add(key=i).value
        md.name = name
        md.stats.add(metadata_id=1, str_value="loop fusion")
        if scope is not None:
            md.stats.add(metadata_id=2, str_value=scope, ref_value=ref)
    space.planes.add(name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert span_reduce._op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.18 = s32[8] fusion()": "jit(_fold_core)/seclud.fold/stage1/while/body",
        "%fusion.5 = s32[8] fusion()": "jit(_fold_core)/seclud.fold/count"}}


def test_psum_per_fold_averaged_over_the_chips(monkeypatch):
    """The cross-chip sum: the union of each chip's ``%psum.<n>`` ops,
    averaged over the chips, per fold."""
    from bench.run import load_metric

    host = [["bench.window", 0, 10_000_000]]
    devices = {}
    for i, wait in enumerate((100_000, 300_000, 500_000, 1_100_000)):
        devices[f"/device:TPU:{i}"] = {
            "ops": [["fusion.1", 0, 1_000_000, "jit(body)/shard_map/seclud.fold/stage1/while"],
                    ["%psum.7", 1_000_000, wait, ""],
                    ["fusion.2", 5_000_000, 1_000_000, "jit(body)/shard_map/seclud.fold/stage1/while"],
                    ["%psum.7", 6_000_000, wait, ""],
                    ["%psum_scatter.1", 8_000_000, 10, ""]],
            "modules": [["jit_body(1)", 0, 1_000_000 + wait],
                        ["jit_body(1)", 5_000_000, 1_000_000 + wait]]}
    monkeypatch.setattr(span_reduce, "load", lambda _d: {"devices": devices, "host": host})
    rec = types.SimpleNamespace(trace={}, window_batches=lambda: [])
    r = span_reduce.for_record(rec)
    assert r["fold_modules"] == 2 and r["search_ns"] == pytest.approx(2_000_000)
    assert r["psum_ns"] == pytest.approx(2 * 500_000)
    assert load_metric("psum_ms.closed")(rec) == pytest.approx(0.5)


def test_shard_skew_over_the_window_batches():
    from bench.run import load_metric

    read = load_metric("shard_skew.closed")
    infos = [{"shard_cells": [10.0, 10.0, 10.0, 10.0]}, {"shard_cells": [40.0, 0.0, 0.0, 0.0]},
             {"shard_cells": [0.0, 0.0, 0.0, 0.0]}, {"cells": 5.0}]
    rec = types.SimpleNamespace(window_batches=lambda: [types.SimpleNamespace(info=i)
                                                        for i in infos])
    assert read(rec) == pytest.approx((1.0 + 4.0) / 2)
    one_chip = types.SimpleNamespace(window_batches=lambda: [types.SimpleNamespace(info={})])
    assert read(one_chip) is None
