"""Fig. 1: deeper MLPs do NOT improve SAC (depth sweep at fixed width),
plus the loss-surface sharpness comparison (Fig. 1b vs 3b).

Paper: Ant-v2, units=256, layers in {1,2,4,8,16}, 1M steps.
Quick: pendulum, units=32, layers in {1, 2, 4}, sharpness at depth 1 vs 4.

The sweep runs on the vmapped fleet driver (``repro.rl.Sweep``): each
depth is its own compiled shape, so ``from_grid`` partitions the grid
into one sub-fleet per depth with the seed replicas batched inside it.
``--sequential``
keeps the legacy one-``Experiment``-at-a-time loop over the SAME specs
for A/B (rows suffixed ``_seq`` so the committed fleet rows survive).
"""
from __future__ import annotations

from benchmarks.common import bench_run, fleet_rows, make_spec


def run(scale: str = "quick", sequential: bool = False):
    layers = [1, 2, 4] if scale == "quick" else [1, 2, 4, 8, 16]
    units = 32 if scale == "quick" else 256
    env = "pendulum" if scale == "quick" else "cartpole_swingup"
    seeds = 5 if scale == "paper" else 1
    base = make_spec(scale, "fig1-depth", env=env, num_units=units)
    if sequential:
        return [bench_run(f"fig1_depth_L{nl}_seq",
                          base.override(num_layers=nl),
                          {"layers": nl, "fleet": False}, seeds=seeds)
                for nl in layers]
    from repro.rl import Sweep
    sweep = Sweep.from_grid(base, axis={"num_layers": layers}, seeds=seeds)
    print(sweep.describe())
    sweep.run(eval_at_end=True)
    return fleet_rows(sweep,
                      lambda pt: f"fig1_depth_L{pt['num_layers']}",
                      lambda pt: {"layers": pt["num_layers"]})


if __name__ == "__main__":
    import argparse

    from benchmarks.common import print_rows
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick")
    ap.add_argument("--sequential", action="store_true",
                    help="legacy per-Experiment loop (A/B vs the fleet)")
    args = ap.parse_args()
    print_rows(run(args.scale, sequential=args.sequential))
