"""The harness end to end on the CPU at rehearsal size, sound and broken."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _cli(args, cwd=ROOT, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_refuses_the_cpu_for_a_measurement():
    p = _cli(["--workload", "wiki.closed", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _cli(["--workload", "wiki.closed", "--seed", "1", "--seconds", "1", "--trace", "0",
              "--rehearse"], cwd=tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_rehearsal_last_line_and_checks():
    p = _cli(["--workload", "wiki.closed", "--seed", "2718281828459", "--seconds", "2",
              "--trace", "1", "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(out["device"])
    assert set(out["metrics"]) >= {"plan_ms.closed", "lower_ms.closed", "fold_rt_ms.closed"}
    assert set(out["checks"]) == {"wrong", "unanswered"}
    assert p.stderr.strip().splitlines()[-2:] == ["check wrong: 0 (limit 0)",
                                                   "check unanswered: 0 (limit 0)"]


def _alter_one(_cq, counts):
    out = np.array(counts, copy=True)
    out[0] += 1
    return out


def _drop_half(_cq, counts):
    return np.asarray(counts)[: len(counts) // 2]


@pytest.mark.parametrize("fault,caught", [(None, None), (_alter_one, "wrong"),
                                          (_drop_half, "unanswered")])
def test_broken_path_is_not_correct(fault, caught):
    cell = run.find_cell("wiki.closed", rehearse=True)
    out = run.run_cell(cell, 77, 1.0, trace=False, rehearse=True, fault=fault, grace_s=3.0)
    assert list(out) == KEYS
    if caught is None:
        assert out["correct"] is True
    else:
        assert out["correct"] is False
        assert out["checks"][caught]["value"] > out["checks"][caught]["limit"]


def test_control_is_not_correct():
    """The reference at 16-bit document ids, in the program's place, on a
    corpus past 65,536 documents: the run reads ``correct: false``."""
    base = run.find_cell("gov2s.closed", rehearse=True)
    cell = dataclasses.replace(base, config={**base.config, "n_docs": 70_000})
    out = run.run_cell(cell, 2**31 + 99, 1.0, trace=False, rehearse=True, control=True)
    assert out["correct"] is False
    assert out["checks"]["wrong"]["value"] > 0 and out["checks"]["unanswered"]["value"] == 0


# -- the four-chip cell, on four virtual CPU devices ------------------------

FOUR = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def test_four_chip_rehearsal_on_a_cold_cache(tmp_path):
    """``wiki4.closed`` serves every batch through the program's sharded
    path over 4 devices, and nothing compiles inside the window even
    with an empty compile cache."""
    p = _cli(["--workload", "wiki4.closed", "--seed", "3000000019", "--seconds", "2",
              "--trace", "0", "--rehearse"],
             env_extra=dict(FOUR, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 4
    assert "setup: sharded n_shards=4 " in p.stdout
    assert "window: sharded n_shards=[4] batches_with_an_idle_shard=0 " in p.stdout
    assert "compiles_in_window fold=0 traces=0 " in p.stdout


@pytest.fixture(scope="module")
def sharded_cases():
    """``sharded_cases.py``'s results, from one process with 4 devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **FOUR)
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "tests" / "sharded_cases.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case,caught", [("sound", None), ("alter_one", "wrong"),
                                         ("drop_half", "unanswered"), ("no_psum", "wrong"),
                                         ("control", "wrong")])
def test_four_chip_broken_path_is_not_correct(sharded_cases, case, caught):
    """On ``wiki4.closed``: an answer altered where it is produced, half
    of a batch left out, the exchange between chips (the psum) left out,
    and the reference at 16-bit document ids in the program's place on a
    corpus past 65,536 documents each read ``correct: false``."""
    out = sharded_cases[case]
    assert out["count"] == 4
    if caught is None:
        assert out["correct"] is True and out["checks"] == {"wrong": 0, "unanswered": 0}
    else:
        assert out["correct"] is False and out["checks"][caught] > 0


def test_four_chip_prewarm_compiles_the_lowered_keys(sharded_cases):
    """The sharded prewarm compiles each shape ``lower_plan_sharded``
    gives the stream's windows once, and a second pass compiles nothing;
    the program keeps one sharded fold per shape without the cell count."""
    pw = sharded_cases["prewarm"]
    assert pw["compiles"] == pw["keys"] > 0
    assert pw["compiles_again"] == 0
    assert pw["cached_folds"] == pw["folds"]
