"""End-to-end driver: the paper's ablation on one screen.

Runs Full / w/o Ape-X / w/o OFENet / w/o DenseNet / original-SAC on the same
env+budget and prints the Fig.-10-style comparison table.

Variants build from the ``rl-distributed`` preset (device-resident replay +
scan superstep by default — the production path) through the layered spec
API. Any spec field is reachable with ``--override key=value`` (repeatable;
dotted paths or legacy flat aliases), replacing the old grown flag list:

    PYTHONPATH=src python examples/rl_distributed.py [--steps 800]
        [--override replay.backend=host] [--override replay.kernel=pallas]
        [--override execution.loop=python] [--override replay.n_step=3]
        [--override network.block_backend=fused]

Telemetry rides the same overrides: ``--override obs.enabled=true
--override obs.sinks=jsonl --override obs.log_dir=runs/abl`` streams
per-variant diagnostics without perturbing the trained bits (see
``repro.obs``).
"""
import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.rl import Experiment, parse_overrides, presets

VARIANTS = {
    "full":        dict(),
    "wo_apex":     dict(distributed=False, n_env=1),
    "wo_ofenet":   dict(use_ofenet=False),
    "wo_densenet": dict(connectivity="mlp"),
    "sac":         dict(connectivity="mlp", use_ofenet=False,
                        distributed=False, n_env=1, num_units=32,
                        activation="relu"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--env", default="pendulum")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="spec override, e.g. replay.backend=host or "
                         "n_step=3 (repeatable)")
    args = ap.parse_args()
    enable_compile_cache()

    overrides = parse_overrides(args.override)
    base = presets.get("rl-distributed").override(
        env=args.env, total_steps=args.steps,
        eval_every=max(args.steps // 2, 1), **overrides)
    r, x, n = base.replay, base.execution, base.network
    print(f"replay backend: {r.backend} ({r.kernel}), loop={x.loop}, "
          f"n_step={r.n_step}, blocks={n.block_backend}")
    print(f"{'variant':<14}{'max return':>12}{'params':>12}")
    for name, ov in VARIANTS.items():
        res = Experiment.from_spec(base.override(**ov)).run(eval_at_end=True)
        print(f"{name:<14}{res.max_return:>12.1f}{res.param_count:>12,}")


if __name__ == "__main__":
    main()
