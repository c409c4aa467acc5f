"""Benchmark harness: one module per paper table/figure (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV per the harness contract and merges
the full rows into experiments/bench_results.json (rows with the same name
are replaced, others are kept, so ``--only`` reruns never drop results).
Every stored row is stamped with a ``host`` fingerprint (platform, CPU
count, jax version/backend) and ``recorded_at``, so a ratio in the
committed JSON is traceable to the box and build that produced it — and
mixed-provenance files are detectable. Ratio rows additionally embed their
same-run baseline (see ``loop_fusion.both_steps_per_sec``: the baseline
reps are interleaved with the measured ones on the same box, so the ratio
is never an artifact of when each side was measured).

  PYTHONPATH=src python -m benchmarks.run [--scale quick|paper] [--only fig5]

``--smoke`` is the CI bitrot guard: the preset registry resolves and builds
every paper scenario (presets_smoke), then one-rep runs of the kernel/loop
benchmarks (dense_stack, loop_fusion) — failures fatal instead of
swallowed, results written to experiments/bench_smoke.json.
"""
import argparse
import datetime
import importlib
import json
import os
import platform
import sys
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache


def host_fingerprint() -> dict:
    """The box + build a row was measured on (stamped into every row)."""
    import jax
    return {"platform": platform.platform(),
            "machine": platform.machine(),
            "cpus": os.cpu_count(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "n_devices": jax.device_count()}

MODULES = [
    "benchmarks.presets_smoke",
    "benchmarks.fig1_depth",
    "benchmarks.fig3_width",
    "benchmarks.fig4_grid",
    "benchmarks.fig5_connectivity",
    "benchmarks.fig6_ofenet",
    "benchmarks.fig8_distributed",
    "benchmarks.fig10_ablation",
    "benchmarks.fig13_activation",
    "benchmarks.table1_final",
    "benchmarks.loss_landscape_bench",
    "benchmarks.kernels_micro",
    "benchmarks.replay_micro",
    "benchmarks.dense_stack",
    "benchmarks.loop_fusion",
    "benchmarks.sweep_fleet",
    "benchmarks.serve_policy",
]

# presets_smoke resolves every paper scenario through the preset registry
# (construct + validate + build the Experiment, no jit) before the
# kernel/loop one-rep runs
SMOKE_MODULES = ["benchmarks.presets_smoke", "benchmarks.dense_stack",
                 "benchmarks.loop_fusion", "benchmarks.serve_policy"]


def _merge_write(path: Path, rows) -> None:
    """Replace same-name rows, keep the rest — --only reruns stay additive."""
    existing = []
    if path.exists():
        try:
            existing = json.loads(path.read_text())
        except Exception:
            existing = []
    new_names = {r["name"] for r in rows}
    merged = [r for r in existing if r.get("name") not in new_names] + rows
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(merged, indent=1, default=str))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick", choices=["quick", "paper"])
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="one-rep kernel/loop benchmarks, failures fatal")
    args = ap.parse_args()
    enable_compile_cache()

    mods = SMOKE_MODULES if args.smoke else MODULES
    if args.only:
        mods = [m for m in mods if args.only in m]
    scale = "smoke" if args.smoke else args.scale
    all_rows, failed = [], []
    print("name,us_per_call,derived")
    for mod_name in mods:
        mod = importlib.import_module(mod_name)
        try:
            rows = mod.run(scale)
        except Exception as e:  # run the other modules, then exit nonzero
            if args.smoke:
                raise
            print(f"{mod_name},0,ERROR:{type(e).__name__}:{e}")
            failed.append(mod_name)
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")
        all_rows.extend(rows)
    stamp = {"host": host_fingerprint(),
             "recorded_at": datetime.datetime.now(
                 datetime.timezone.utc).isoformat(timespec="seconds")}
    all_rows = [{**r, **stamp} for r in all_rows]
    out = Path("experiments/bench_smoke.json" if args.smoke
               else "experiments/bench_results.json")
    _merge_write(out, all_rows)
    if failed:
        print(f"benchmarks.run: {len(failed)} module(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
