import json
from pathlib import Path

import pytest

from bench import trace_reduce

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
