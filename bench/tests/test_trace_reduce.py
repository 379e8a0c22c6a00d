import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import fold_bytes, trace_reduce
from bench.run import load_metric

RECORDED = Path(__file__).with_name("recorded_trace.json")


def test_hand_built_trace():
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 0, 10], ["fusion.2", 5, 15], ["copy", 30, 10]],
            "modules": [["jit__fold_core(1)", 0, 20], ["jit_other(2)", 30, 10]]}},
        "host": [["bench.window", 0, 100], ["bench.dispatch", 20, 15]],
    }
    r = trace_reduce.reduce(trace)
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["fold_device_s"] == pytest.approx(20e-9) and r["fold_modules"] == 1
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(15e-9)]
    assert r["idle_gaps"] == [["no host span", pytest.approx(60e-9)],
                              ["bench.dispatch", pytest.approx(10e-9)]]


def test_events_outside_the_window_do_not_count():
    trace = {"devices": {"/device:TPU:0": {"ops": [["a", -50, 60], ["b", 90, 30]],
                                           "modules": []}},
             "host": [["bench.window", 0, 100]]}
    r = trace_reduce.reduce(trace)
    assert r["busy_s"] == pytest.approx(20e-9)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    """A slice of a real one-chip trace of ``wiki.closed``: the numbers
    written beside it were read from it by hand."""
    rec = json.loads(RECORDED.read_text())
    r = trace_reduce.reduce(rec["trace"])
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["busy_s"] < r["window_s"]


def test_sharded_fold_modules_per_chip():
    """The sharded fold's module (``jit_body``) is a fold on every chip;
    times are per chip, averaged over the chips."""
    devices = {f"/device:TPU:{i}": {
        "ops": [["fusion.1", 0, 10 + i], ["psum.7", 10 + i, 2]],
        "modules": [["jit_body(7)", 0, 12 + i], ["jit_convert(2)", 30, 10]]} for i in range(4)}
    r = trace_reduce.reduce({"devices": devices, "host": [["bench.window", 0, 100]]})
    assert r["n_devices"] == 4 and r["fold_modules"] == 1
    assert r["fold_device_s"] == pytest.approx(13.5e-9)
    assert not trace_reduce.is_fold("jit_body_of_something(3)")
    assert trace_reduce.is_fold("jit__fold_core(13905777743827717586)")


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_roofline_over_the_chips_bandwidth():
    """On the recorded one-chip trace the roofline reads, bit for bit,
    what it read before it took the chips into account; on four chips
    the same bytes and time read a quarter of it."""
    t = trace_reduce.reduce(json.loads(RECORDED.read_text())["trace"])
    arities, want = np.array([2, 2, 2, 2]), np.array([1200, 45, 0, 310])
    rec = types.SimpleNamespace(
        trace=t, peaks={"hbm_bytes_per_s": 819e9}, arities=arities, want=want, chips=1,
        requests=types.SimpleNamespace(qid=np.arange(4), count=np.array([1200, 45, 0, -1])))
    read = load_metric("fold_roofline.closed")
    need = fold_bytes.needed_bytes(arities[:3], want[:3])
    assert read(rec) == 100.0 * need / 819e9 / t["fold_device_s"]
    rec.chips = 4
    assert read(rec) == pytest.approx(100.0 * need / 819e9 / t["fold_device_s"] / 4, rel=1e-15)
