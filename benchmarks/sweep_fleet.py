"""Fleet-vs-sequential throughput: N seeds as ONE vmapped device program
vs N sequential ``Experiment.run``s of the same spec.

The fleet driver (``repro.rl.sweep``) advances all members through one
jitted ``lax.scan`` chunk whose body is ``jax.vmap`` of the Trainer
superstep, so a whole seed battery costs one dispatch per chunk and its
members' matmuls fuse into batched ops — against the sequential loop's
N full dispatch/epilogue costs per chunk. At smoke-scale dims the
superstep is op-overhead-bound, which is exactly the figure-sweep regime
the paper's grids run in on CPU.

What amortizes and what doesn't (measured on the 1-CPU reference box):
gathers, batched GEMMs, per-chunk dispatch and the vmapped eval all get
cheaper per member as M grows; per-member PRNG (threefry) and env
physics are genuinely linear; and PER's sum-tree scatter is serial
per-element on CPU so prioritized replay scales ~8x at M=8 — which is
why the ``fleet-smoke`` preset runs uniform replay and small
batch/capacity. The Fleet driver's done-mask select happens once per
SEGMENT, not per scan step, so the scan body keeps its in-place replay
updates and an all-live fleet pays nearly nothing for freeze support.

Timed end to end through the PUBLIC surfaces (``Fleet.run`` vs
``Experiment.run``, host epilogue work included) with the reps of both
legs INTERLEAVED and min-of-reps taken — the reported ratio is never an
artifact of when each leg was measured. The first pass of each leg
compiles + warms and is excluded.

  PYTHONPATH=src python -m benchmarks.sweep_fleet
"""
from __future__ import annotations

import time


def _spec(steps: int):
    # keep the preset's own eval cadence (every 32 steps): each pass is a
    # CHUNKED run like a real sweep, so the sequential leg pays its
    # per-chunk dispatch/epilogue N times per chunk where the fleet pays
    # once — that amortization is part of what's being measured
    from repro.rl import presets
    return presets.get("fleet-smoke").override(total_steps=steps)


def _fleet_pass(spec, members: int, steps: int):
    from repro.rl import Fleet
    fleet = Fleet([spec.override(seed=s) for s in range(members)])
    fleet.run(steps)                         # compile + warm
    def one():
        t0 = time.time()
        fleet.run(steps)
        return time.time() - t0
    return one


def _sequential_pass(spec, members: int, steps: int):
    from repro.rl import Experiment
    exps = [Experiment.from_spec(spec.override(seed=s))
            for s in range(members)]
    for e in exps:                           # compile + warm
        e.run(steps)
    def one():
        t0 = time.time()
        for e in exps:
            e.run(steps)
        return time.time() - t0
    return one


def fleet_vs_sequential(members: int = 8, steps: int = 256,
                        reps: int = 3) -> dict:
    """Member-steps/sec for both legs, reps interleaved, best-of-reps.
    Keys: "sequential", "fleet"."""
    spec = _spec(steps)
    ones = {"sequential": _sequential_pass(spec, members, steps),
            "fleet": _fleet_pass(spec, members, steps)}
    best = {leg: float("inf") for leg in ones}
    for _ in range(reps):
        for leg, one in ones.items():
            best[leg] = min(best[leg], one())
    return {leg: members * steps / b for leg, b in best.items()}


def run(scale: str = "quick"):
    members = 8
    steps = {"smoke": 32, "quick": 256}.get(scale, 1024)
    reps = 1 if scale == "smoke" else 5   # min-of-5: the box is noisy
    sps = fleet_vs_sequential(members, steps, reps)
    ratio = sps["fleet"] / sps["sequential"]
    base = {"members": members, "steps_per_pass": steps, "reps": reps}
    return [
        {"name": f"sweep_fleet_seq{members}",
         "us_per_call": 1e6 / sps["sequential"],
         "derived": f"{sps['sequential']:.0f}_steps/s", **base},
        {"name": f"sweep_fleet_fleet{members}",
         "us_per_call": 1e6 / sps["fleet"],
         "derived": f"{sps['fleet']:.0f}_steps/s_x{ratio:.1f}",
         "ratio_vs_sequential": round(ratio, 2),
         "baseline_steps_per_sec": round(sps["sequential"], 1), **base},
    ]


if __name__ == "__main__":
    from benchmarks.common import print_rows
    print_rows(run())
