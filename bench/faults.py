"""Faults planted in the program under test, to show that ``correct``
catches them (``bench/readings.py`` on the chip, the tests on the CPU).

* ``unchanged``: the SAC update returns its state unchanged;
* ``half_batch``: the update sees the first half of the batch only, its
  means taken over that half (the priorities of the dropped half repeat
  those of the kept half);
* ``<fault>.window``: the same fault in the window's chunk programs only
  (every chunk of more than one update), the 1-update chunks sound;
* ``program``: nothing planted.
"""
from __future__ import annotations

import contextlib


def _unchanged(orig):
    def update(state, cfg, batch, key):
        _, metrics = orig(state, cfg, batch, key)
        return state, metrics
    return update


def _half_batch(orig):
    import jax.numpy as jnp

    def update(state, cfg, batch, key):
        h = batch["obs"].shape[0] // 2
        state, metrics = orig(state, cfg,
                              {k: v[:h] for k, v in batch.items()}, key)
        p = metrics["priorities"]
        return state, dict(metrics, priorities=jnp.concatenate([p, p]))
    return update


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch}


@contextlib.contextmanager
def planted(fault: str):
    """Patch the program for the duration of the block. The trainer looks
    the update up when it is built, and a chunk program when it is first
    traced, so build and run them inside the block."""
    from repro.rl import runner
    from repro.rl import sac as sac_mod
    saved = (sac_mod.sac_update, runner.Trainer.chunk_fn)
    name, _, where = fault.partition(".")
    if name == "program":
        pass
    elif name not in FAULTS or where not in ("", "window"):
        raise ValueError(f"unknown fault {fault!r}")
    elif not where:
        sac_mod.sac_update = FAULTS[name](saved[0])
    else:
        orig_chunk = saved[1]

        def chunk_fn(self, n_steps, do_eval, do_srank=False):
            fn = orig_chunk(self, n_steps, do_eval, do_srank)
            if n_steps == 1:
                return fn

            def faulty(ls):
                sound = self.update_fn
                self.update_fn = FAULTS[name](sound)
                try:
                    return fn(ls)
                finally:
                    self.update_fn = sound
            return faulty
        runner.Trainer.chunk_fn = chunk_fn
    try:
        yield
    finally:
        sac_mod.sac_update, runner.Trainer.chunk_fn = saved
