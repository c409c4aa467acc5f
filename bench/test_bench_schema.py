"""BENCHMARK.json and the files its cells name."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import harness

BM = harness.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves(workload):
    from repro.rl.experiment import ExperimentSpec
    c = harness.cell(workload)
    spec = c["config"]["spec"]
    # every field explicit: the file equals the spec it builds, so a later
    # change to a preset or a default cannot move the benchmark
    built = ExperimentSpec.from_dict(spec).to_dict()
    assert json.loads(json.dumps(built)) == spec
    assert (harness.BENCH / "kinds" / f"{c['traffic']['kind']}.py").exists()
    for key in ("source", "reduced", "assumed", "deployment",
                "hyperparameters", "env_dims"):
        assert key in c["config"], key
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    for m in c["per_layer"]:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
    assert isinstance(c["limits"], dict)


def test_names_and_shape():
    names = ([m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
             + CELLS + [c["name"] for c in BM["configs"]])
    assert len(names) == len(set(names))
    ok = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
             "0123456789_.-")
    for n in names:
        assert set(n) <= ok and len(n) <= 64, n
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    for w in BM["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert os.path.getsize(harness.ROOT / "BENCHMARK.json") < 64 * 1024


def test_run_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=harness.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
