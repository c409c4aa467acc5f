"""Fig. 3: wider MLPs DO improve SAC (width sweep at fixed depth 2).

Paper: Ant-v2, layers=2, units in {128..2048}. Quick: pendulum, {16,64,256}.

Runs on the vmapped fleet driver (``repro.rl.Sweep``): widths change the
compiled shape, so ``from_grid`` builds one sub-fleet per width with the
seed replicas vmapped inside it. ``--sequential`` keeps the legacy loop
over the same specs for A/B (rows suffixed ``_seq``).
"""
from benchmarks.common import bench_run, fleet_rows, make_spec


def run(scale: str = "quick", sequential: bool = False):
    units = [16, 64, 256] if scale == "quick" else [128, 256, 512, 1024,
                                                    2048]
    seeds = 5 if scale == "paper" else 1
    base = make_spec(scale, "fig3-width")
    if sequential:
        return [bench_run(f"fig3_width_U{nu}_seq",
                          base.override(num_units=nu),
                          {"units": nu, "fleet": False}, seeds=seeds)
                for nu in units]
    from repro.rl import Sweep
    sweep = Sweep.from_grid(base, axis={"num_units": units}, seeds=seeds)
    print(sweep.describe())
    sweep.run(eval_at_end=True)
    return fleet_rows(sweep,
                      lambda pt: f"fig3_width_U{pt['num_units']}",
                      lambda pt: {"units": pt["num_units"]})


if __name__ == "__main__":
    import argparse

    from benchmarks.common import print_rows
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick")
    ap.add_argument("--sequential", action="store_true",
                    help="legacy per-Experiment loop (A/B vs the fleet)")
    args = ap.parse_args()
    print_rows(run(args.scale, sequential=args.sequential))
