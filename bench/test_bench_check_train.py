"""``correct`` for the training cells, on the CPU at a tiny size: a sound
run passes and the bfloat16 control fails (the planted faults are in
test_bench_faults_train.py)."""
from __future__ import annotations

import jax
import pytest

import bench_tiny
import harness
import run

CELLS = [w["name"] for w in harness.benchmark()["workloads"]
         if harness.cell(w["name"])["traffic"]["kind"] == "train_window"]


def _run(c, seed):
    return run.run_cell(c, seed, 0.3, False, devices=jax.devices()[:1])


def _sound(workload, paper):
    r = _run(bench_tiny.tiny_cell(workload, paper), 2 ** 31 + 12345)
    assert r["result"]["correct"], r["checks"]
    assert r["result"]["metrics"]["updates_per_s"]["value"] > 0
    # the window's chunk programs reproduce the 1-update chain exactly here
    assert r["checks"]["chunk_gap"]["value"] == 0.0, r["checks"]
    assert r["checks"]["chunk_gap.eval"]["value"] == 0.0, r["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    _sound(workload, paper=False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_with_the_papers_method_is_correct(workload):
    _sound(workload, paper=True)


@pytest.mark.parametrize("workload", CELLS)
def test_bf16_control_fails(workload):
    import readings
    c = bench_tiny.tiny_cell(workload)
    nums = readings.train_reading(bench_tiny.ctx(c, 5), "control")
    # the control stands in for the reference comparison; it runs no
    # chunk program, so it is judged on the numbers it reads
    assert "chunk_gap" not in nums and "delta_gap" in nums
    limits = {k: v for k, v in c["limits"].items() if k in nums}
    assert not harness.judge(nums, limits)["correct"], nums
