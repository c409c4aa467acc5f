"""``correct`` for the paper's agent's cell on the CPU at a tiny size: a
sound run passes, and the bfloat16 control and a run with ``half_batch``
planted do not (as test_bench_check_train.py and
test_bench_faults_train.py do for ``train_window``)."""
from __future__ import annotations

import jax

import bench_tiny
import faults
import harness
import run

CELL = "ours-u2048-train"
KIND = harness.kind_module("train_window_ours")


def _run(c, seed):
    return run.run_cell(c, seed, 0.3, False, devices=jax.devices()[:1])


def test_sound_run_is_correct():
    r = _run(bench_tiny.tiny_cell(CELL), 2 ** 31 + 12345)
    assert r["result"]["correct"], r["checks"]
    assert r["result"]["metrics"]["updates_per_s"]["value"] > 0
    # the window's chunk programs reproduce the 1-update chain exactly here
    assert r["checks"]["chunk_gap"]["value"] == 0.0, r["checks"]
    assert r["checks"]["chunk_gap.eval"]["value"] == 0.0, r["checks"]


def test_bf16_control_fails():
    c = bench_tiny.tiny_cell(CELL)
    nums = KIND.train_reading(bench_tiny.ctx(c, 5), "control")
    assert "chunk_gap" not in nums and "delta_gap" in nums
    limits = {k: v for k, v in c["limits"].items() if k in nums}
    assert not harness.judge(nums, limits)["correct"], nums


def test_half_batch_is_caught():
    c = bench_tiny.tiny_cell(CELL)
    with faults.planted("half_batch"):
        r = _run(c, 77)
    assert not r["result"]["correct"], r["checks"]
