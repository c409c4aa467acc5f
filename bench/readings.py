"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \
        --modes program,control,half_batch,half_batch.window

For each seed and mode it prints one JSON line with the numbers the cell
compares (``bench/kinds/train_window.py``'s ``compare``):

* ``program``: the program as the cell runs it (its set-up: the window's
  chunk programs and the 1-update chain beside them), against the
  reference;
* ``control``: the reference computed in bfloat16 (weights, optimizer
  state, activations) in the program's place, against the float32
  reference; it has no chunk programs, so it reads no ``chunk_gap``;
* a fault of ``bench/faults.py`` (``unchanged``, ``half_batch``, and
  either in the window's chunk programs only, ``<fault>.window``): the
  program with that fault planted.

Training needs no measured window: the readings come from the set-up.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import faults  # noqa: E402
import harness  # noqa: E402


def train_reading(ctx, mode: str) -> dict:
    tw = harness.kind_module("train_window")
    if mode == "control":
        return tw.compare(tw.reference(ctx, "bfloat16"), tw.reference(ctx))
    with faults.planted(mode):
        st = tw.setup(ctx)
    readings = st["readings"]
    del st
    gc.collect()
    return tw.compare(readings, tw.reference(ctx))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--modes", default="program,control")
    a = p.parse_args(argv)
    c = harness.cell(a.workload)
    devices = harness.require_chips(c["workload"]["chips"])
    harness.enable_cache()
    counter = harness.CompileCounter()
    worst = {}
    for mode in a.modes.split(","):
        for seed in [int(s) for s in a.seeds.split(",")]:
            ctx = {**c, "seed": seed, "trace": False,
                   "workload": a.workload, "devices": devices,
                   "compiles": counter}
            nums = train_reading(ctx, mode)
            print(json.dumps({"workload": a.workload, "mode": mode,
                              "seed": seed, "numbers": nums}), flush=True)
            w = worst.setdefault(mode, {})
            for k, v in nums.items():
                w[k] = max(w.get(k, -np.inf), v)
            gc.collect()
    print(json.dumps({"workload": a.workload, "max_by_mode": worst}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
