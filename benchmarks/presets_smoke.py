"""Preset-registry smoke: every paper scenario constructs, validates, and
builds its ``Experiment`` (Trainer + replay wiring), with NO jit execution —
the CI bitrot guard for the spec/preset layer, mirroring the tier-1 test in
tests/test_experiment.py. Emits one row per preset (build wall time)."""
from __future__ import annotations

import time


def run(scale: str = "quick"):
    from repro.rl import Experiment, presets

    rows = []
    for name in presets.names():
        t0 = time.time()
        spec = presets.get(name)
        exp = Experiment.from_spec(spec)
        assert exp.step == 0 and exp._ls is None  # built, nothing executed
        # the spec round-trips through its own serialization
        assert type(spec).from_dict(spec.to_dict()) == spec
        rows.append({"name": f"preset_build_{name}",
                     "us_per_call": 1e6 * (time.time() - t0),
                     "env": spec.env, "algo": spec.algo})
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows
    print_rows(run())
