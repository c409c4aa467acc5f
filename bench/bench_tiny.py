"""Tiny copies of the benchmark's cells for the CPU tests: the same
configuration files with the widths, replay and batch cut so that a whole
run (set-up, window, reference, comparison) takes seconds. The limits are
the cell's own. ``paper=True`` turns on the paper's method on top (OFENet,
the DenseNet trunk with swish, a 2 x 2 Ape-X actor pool), so that the
program's and the reference's paths for it stay checked against each
other."""
from __future__ import annotations

import harness


def tiny_cell(workload: str, paper: bool = False) -> dict:
    c = harness.cell(workload)
    s = c["config"]["spec"]
    s["network"]["num_units"] = 16
    s["ofenet"].update(num_units=8, num_layers=2)
    s["replay"]["capacity"] = 1000
    s["execution"].update(batch_size=16, warmup_steps=64)
    s["eval"].update(every=16, episodes=2)
    if paper:
        s["network"].update(connectivity="densenet", activation="swish")
        s["ofenet"]["enabled"] = True
        s["execution"].update(distributed=True, n_core=2, n_env=2)
    c["traffic"] = dict(c["traffic"], chunk_steps=8, trace_chunks=1)
    return c


def ctx(c: dict, seed: int) -> dict:
    import jax
    return {**c, "seed": seed, "trace": False,
            "workload": c["workload"]["name"], "devices": jax.devices()[:1],
            "compiles": harness.CompileCounter()}
