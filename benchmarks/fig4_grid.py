"""Fig. 4: (units x layers) grid of max average return.

Paper: 5x5 grid on Ant-v2. Quick: 2x2 {32,128} x {1,4} on pendulum.

Runs on the vmapped fleet driver (``repro.rl.Sweep``): every (units,
layers) cell is its own compiled shape, so ``from_grid`` partitions the
full cartesian grid into one sub-fleet per cell, seeds vmapped inside.
``--sequential`` keeps the legacy loop over the same specs for A/B
(rows suffixed ``_seq``).
"""
from benchmarks.common import bench_run, fleet_rows, make_spec


def run(scale: str = "quick", sequential: bool = False):
    units = [32, 128] if scale == "quick" else [128, 256, 512, 1024, 2048]
    layers = [1, 4] if scale == "quick" else [1, 2, 4, 8, 16]
    seeds = 5 if scale == "paper" else 1
    base = make_spec(scale, "fig4-grid")
    if sequential:
        return [bench_run(f"fig4_grid_U{nu}_L{nl}_seq",
                          base.override(num_units=nu, num_layers=nl),
                          {"units": nu, "layers": nl, "fleet": False},
                          seeds=seeds)
                for nu in units for nl in layers]
    from repro.rl import Sweep
    sweep = Sweep.from_grid(
        base, axis={"num_units": units, "num_layers": layers}, seeds=seeds)
    print(sweep.describe())
    sweep.run(eval_at_end=True)
    return fleet_rows(
        sweep,
        lambda pt: f"fig4_grid_U{pt['num_units']}_L{pt['num_layers']}",
        lambda pt: {"units": pt["num_units"], "layers": pt["num_layers"]})


if __name__ == "__main__":
    import argparse

    from benchmarks.common import print_rows
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick")
    ap.add_argument("--sequential", action="store_true",
                    help="legacy per-Experiment loop (A/B vs the fleet)")
    args = ap.parse_args()
    print_rows(run(args.scale, sequential=args.sequential))
