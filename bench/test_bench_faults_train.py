"""``correct`` for the training cells, on the CPU at a tiny size: a run
with a fault of ``bench/faults.py`` planted in the program comes out not
correct, whether the fault is in every chunk program or only in the
window's."""
from __future__ import annotations

import jax
import pytest

import bench_tiny
import faults
import harness
import run

CELLS = [w["name"] for w in harness.benchmark()["workloads"]
         if harness.cell(w["name"])["traffic"]["kind"] == "train_window"]


def _run(c, seed):
    return run.run_cell(c, seed, 0.3, False, devices=jax.devices()[:1])


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "unchanged.window", "half_batch.window"])
def test_fault_is_caught(workload, fault):
    c = bench_tiny.tiny_cell(workload)
    with faults.planted(fault):
        r = _run(c, 77)
    assert not r["result"]["correct"], r["checks"]
    if fault.endswith(".window"):
        # the 1-update chain is sound: only the chunk comparison sees it
        assert r["checks"]["chunk_gap"]["value"] > 0, r["checks"]
        assert r["checks"]["delta_gap"]["value"] <= \
            c["limits"]["delta_gap"], r["checks"]
