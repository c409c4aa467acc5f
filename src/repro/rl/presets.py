"""Paper-scenario preset registry.

Every paper figure/table scenario is a named ``ExperimentSpec`` so drivers
stop hand-building configs: ``presets.get("fig5-connectivity")`` returns the
validated base spec for that scenario and sweeps derive variants with
``.override(...)`` (dotted paths or flat aliases). All presets carry
the CPU-quick budget (runnable on a laptop); ``benchmarks/common.py`` scales
them to the paper budget for real hardware, and every preset must build an
``Experiment`` without executing jit (enforced by a tier-1 test and
``benchmarks.run --smoke``).

    from repro.rl import presets
    exp = Experiment.from_spec(presets.get("fig3-width").override(
        num_units=1024))

Presets ship with telemetry off; attach it per run with dotted overrides
(``.override(**{"obs.enabled": True, "obs.sinks": ("jsonl",),
"obs.log_dir": "runs/exp0"})`` — see ``repro.obs``).

Names follow the paper artifacts: ``fig1-depth``, ``fig3-width``,
``fig4-grid``, ``fig5-connectivity``, ``fig6-ofenet``, ``fig8-distributed``,
``fig10-ablation``, ``fig13-activation``, ``table1-ours``, ``table1-orig``,
plus the repo's own end-to-end scenarios ``quickstart``,
``rl-distributed`` and ``smoke`` (tiny CI dims). ``register`` adds
project-local scenarios.
"""
from __future__ import annotations

from typing import Callable, Dict, Union

from repro.rl.experiment import ExperimentSpec, SpecError

# the CPU-quick budget shared by every preset (mirrors the historical
# benchmarks/common.py QUICK dict; benchmarks scale past it for "paper")
_QUICK_BUDGET = dict(total_steps=500, warmup_steps=250, eval_every=125,
                     eval_episodes=3, replay_capacity=50_000,
                     batch_size=128, n_core=1, n_env=16, ofenet_units=16,
                     ofenet_layers=2)

_BASE = ExperimentSpec().override(**_QUICK_BUDGET)

_PRESETS: Dict[str, ExperimentSpec] = {
    # one preset per paper scenario; the swept axis stays at its base value
    # and figure drivers override it per row
    "fig1-depth": _BASE.override(
        algo="sac", num_units=32, num_layers=2, connectivity="mlp",
        use_ofenet=False, distributed=False, srank_every=150),
    "fig3-width": _BASE.override(
        algo="sac", num_units=64, num_layers=2, connectivity="mlp",
        use_ofenet=False, distributed=False, srank_every=150),
    "fig4-grid": _BASE.override(
        algo="sac", num_units=32, num_layers=1, connectivity="mlp",
        use_ofenet=False, distributed=False),
    "fig5-connectivity": _BASE.override(
        algo="sac", num_units=32, num_layers=2, connectivity="densenet",
        use_ofenet=False, distributed=False, srank_every=150),
    "fig6-ofenet": _BASE.override(
        algo="sac", num_units=32, num_layers=2, connectivity="densenet",
        use_ofenet=True, distributed=False, srank_every=150),
    "fig8-distributed": _BASE.override(
        algo="sac", num_units=32, num_layers=2, connectivity="densenet",
        use_ofenet=True, distributed=True, n_core=2, n_env=16),
    "fig10-ablation": _BASE.override(
        algo="sac", num_units=128, num_layers=2, connectivity="densenet",
        use_ofenet=True, distributed=True, n_core=2, n_env=16),
    "fig13-activation": _BASE.override(
        algo="sac", num_units=64, num_layers=2, connectivity="densenet",
        activation="swish", use_ofenet=True, distributed=False),
    # Table 1: the paper's full method vs the original small-MLP baselines
    "table1-ours": _BASE.override(
        num_units=128, num_layers=2, connectivity="densenet",
        use_ofenet=True, distributed=True, n_core=2, n_env=16),
    "table1-orig": _BASE.override(
        num_units=32, num_layers=2, connectivity="mlp", activation="relu",
        use_ofenet=False, distributed=False, n_env=1),
    # repo end-to-end scenarios
    "quickstart": _BASE.override(
        algo="sac", num_units=128, num_layers=2, connectivity="densenet",
        use_ofenet=True, ofenet_units=32, ofenet_layers=4,
        distributed=True, n_core=2, n_env=16, total_steps=1000,
        warmup_steps=300, eval_every=125, srank_every=125),
    "rl-distributed": _BASE.override(
        algo="sac", num_units=128, num_layers=2, connectivity="densenet",
        use_ofenet=True, ofenet_units=32, ofenet_layers=2,
        distributed=True, n_core=2, n_env=16, total_steps=800,
        warmup_steps=300, eval_every=400),
    "smoke": _BASE.override(
        num_units=16, num_layers=1, use_ofenet=False, n_core=1, n_env=4,
        total_steps=12, warmup_steps=8, eval_every=6, eval_episodes=1,
        replay_capacity=256, batch_size=16),
    # fleet-ready tiny scenario for CI fleet smoke + benchmarks/sweep_fleet.py
    # dims sit in the op-overhead-bound regime where fleet batching pays:
    # uniform replay (the PER sum-tree's scatter writes are serial
    # per-element on CPU and scale linearly under vmap — see the
    # repro.rl.sweep docstring) and small batch/capacity so per-member
    # compute stays below the per-op fixed cost the fleet amortizes
    "fleet-smoke": _BASE.override(
        num_units=16, num_layers=1, use_ofenet=False, n_core=1, n_env=4,
        total_steps=64, warmup_steps=16, eval_every=32, eval_episodes=1,
        replay_capacity=256, batch_size=8, prioritized=False),
}


def names() -> tuple:
    return tuple(sorted(_PRESETS))


def get(name: str) -> ExperimentSpec:
    """The named scenario's base spec (immutable; derive with .override)."""
    if name not in _PRESETS:
        raise SpecError(f"unknown preset {name!r}; have {sorted(_PRESETS)}")
    return _PRESETS[name]


def register(name: str,
             spec: Union[ExperimentSpec,
                         Callable[[], ExperimentSpec]]) -> None:
    """Add a project-local scenario (callables are resolved immediately so
    registration fails fast on an invalid spec)."""
    if name in _PRESETS:
        raise SpecError(f"preset {name!r} already registered")
    if callable(spec):
        spec = spec()
    if not isinstance(spec, ExperimentSpec):
        raise SpecError(f"preset {name!r} must be an ExperimentSpec, got "
                        f"{type(spec).__name__}")
    _PRESETS[name] = spec
