"""Shared benchmark scaffolding over the preset registry.

Every benchmark module exposes ``run(scale) -> list[dict]`` where scale
in {"quick", "paper"}: "quick" is CPU-budget (reduced nets/steps, 1 seed),
"paper" matches the paper's settings (1M steps, 5 seeds) for real hardware.
Rows are printed by run.py as ``name,us_per_call,derived`` CSV.

Scenario configs resolve through ``repro.rl.presets`` — drivers call
``make_spec(scale, "fig5-connectivity", num_units=2048, ...)`` which takes
the named preset, applies the scale budget, then the per-row overrides
(dotted spec paths or legacy flat aliases), and ``bench_run`` drives the
result through the resumable ``Experiment`` handle.
"""
from __future__ import annotations

import time
from typing import Dict, List

from repro.rl import Experiment, ExperimentSpec, presets

# presets bake the CPU-quick budget (and scenario-specific actor pools);
# the paper budget lifts the fields quick shrank, on top of the 1M-step
# settings, WITHOUT touching scenario knobs like n_core/n_env
PAPER = dict(total_steps=1_000_000, warmup_steps=10_000, eval_every=10_000,
             eval_episodes=10, replay_capacity=100_000, batch_size=256,
             ofenet_units=64, ofenet_layers=4)


def make_spec(scale: str, preset: str, **overrides) -> ExperimentSpec:
    """Preset -> scale budget -> per-row overrides, validated end to end.

    Only "paper" opts into the 1M-step settings; anything else (quick,
    smoke, unknown) stays on the CPU budget baked into the presets."""
    budget = PAPER if scale == "paper" else {}
    return presets.get(preset).override(**{**budget, **overrides})


def bench_run(name: str, spec: ExperimentSpec, extra: Dict = None,
              seeds: int = 1) -> Dict:
    t0 = time.time()
    results = []
    for i in range(seeds):
        exp = Experiment.from_spec(
            spec.override(seed=spec.execution.seed + i))
        results.append(exp.run(eval_at_end=True))
    wall = time.time() - t0
    maxes = [r.max_return for r in results]
    import numpy as np
    total = spec.execution.total_steps
    row = {
        "name": name,
        "us_per_call": 1e6 * wall / max(total * seeds, 1),
        "derived": round(float(np.mean(maxes)), 2),   # mean over seeds of max
        "std": round(float(np.std(maxes)), 2),
        "final_return": round(float(np.mean([r.final_return
                                             for r in results])), 2),
        "params": results[0].param_count,
        "srank": results[-1].sranks[-1] if results[-1].sranks else "",
        "seeds": seeds,
    }
    row.update(extra or {})
    return row


def fleet_rows(sweep, name_fn, extra_fn=None) -> List[Dict]:
    """Aggregate a finished ``repro.rl.Sweep`` into ``bench_run``-schema
    rows: one row per sub-fleet (= per grid point — ``from_grid`` groups a
    point's seed replicas into one fleet), seeds aggregated the same way
    ``bench_run`` aggregates its sequential seed loop, and ``us_per_call``
    normalized per member-step from the fleet's shared wall clock."""
    import numpy as np
    rows = []
    for fl in sweep.fleets:
        results = fl.results()
        maxes = [r.max_return for r in results]
        point = fl.points[0]
        row = {
            "name": name_fn(point),
            "us_per_call": 1e6 * fl._wall / max(fl.step * fl.n_members, 1),
            "derived": round(float(np.mean(maxes)), 2),
            "std": round(float(np.std(maxes)), 2),
            "final_return": round(float(np.mean(
                [r.final_return for r in results])), 2),
            "params": results[0].param_count,
            "srank": results[-1].sranks[-1] if results[-1].sranks else "",
            "seeds": fl.n_members,
            "fleet": True,
        }
        if extra_fn:
            row.update(extra_fn(point))
        rows.append(row)
    return rows


def print_rows(rows: List[Dict]) -> None:
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.0f},"
              f"{r.get('derived', '')}")
