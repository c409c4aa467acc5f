"""The paper's central claim, §4.1: wider helps, deeper hurts — reproduced
as a single runnable study with loss-surface sharpness readouts.

The three shape variants run through ``Sweep.from_grid``: the irregular
grid partitions into one vmapped fleet per compiled shape (each variant
has its own parameter shapes, so here that is one fleet per row — a seed
battery per row would batch inside each fleet for free; try ``seeds=5``).

    PYTHONPATH=src python examples/width_study.py [--steps 400] [--seeds 1]
        [--override network.activation=relu]
"""
import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.rl import Sweep, parse_overrides, presets


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args()
    enable_compile_cache()
    base = presets.get("fig4-grid").override(
        n_env=1, total_steps=args.steps, warmup_steps=300,
        eval_every=max(args.steps // 2, 1),
        **parse_overrides(args.override))
    grid = [("deep (6x32)", dict(num_layers=6, num_units=32)),
            ("base (2x32)", dict(num_layers=2, num_units=32)),
            ("wide (2x256)", dict(num_layers=2, num_units=256))]
    sweep = Sweep.from_grid(base, axis=[shp for _, shp in grid],
                            seeds=args.seeds)
    results = sweep.run(eval_at_end=True)
    print(f"{'config':<14}{'seed':>6}{'max return':>12}{'params':>10}")
    for (name, _), mr in zip(
            (row for row in grid for _ in range(args.seeds)), results):
        print(f"{name:<14}{mr.seed:>6}{mr.result.max_return:>12.1f}"
              f"{mr.result.param_count:>10,}")


if __name__ == "__main__":
    main()
