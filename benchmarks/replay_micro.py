"""Replay microbenchmark: the device replay (repro.replay).

Per-op wall time for the Ape-X replay hot loop — ``add`` / ``sample`` /
``update`` — swept over capacity 2^14..2^20 ("quick" trims the sweep for
CPU CI). ``derived`` reports sampled transitions per second. The replay is
timed through its jitted functional ops with the XLA tree (CPU-honest; the
Pallas kernel is timed at the smallest capacity only — interpret mode is a
correctness harness, not a speed proxy — and its TPU story is the
roofline's).

  PYTHONPATH=src python -m benchmarks.replay_micro
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 256


def _mk_batch(n, obs_dim=8, act_dim=2, seed=0):
    rng = np.random.default_rng(seed)
    return {"obs": rng.normal(size=(n, obs_dim)).astype(np.float32),
            "act": rng.normal(size=(n, act_dim)).astype(np.float32),
            "rew": rng.normal(size=(n,)).astype(np.float32),
            "next_obs": rng.normal(size=(n, obs_dim)).astype(np.float32),
            "done": np.zeros((n,), np.float32)}


def _time(fn, reps):
    fn()                                   # warmup / compile
    t0 = time.time()
    for _ in range(reps):
        fn()
    return 1e6 * (time.time() - t0) / reps


def _bench_device(capacity, reps, backend):
    from repro.replay import (DeviceReplayConfig, replay_add, replay_init,
                              replay_sample, replay_update)
    cfg = DeviceReplayConfig(capacity=capacity, obs_dim=8, act_dim=2,
                             backend=backend)
    state = replay_init(cfg)
    state = replay_add(cfg, state, {k: jnp.asarray(v) for k, v in
                                    _mk_batch(capacity // 2, seed=0).items()})
    batch = {k: jnp.asarray(v) for k, v in _mk_batch(BATCH, seed=1).items()}

    def add():
        jax.block_until_ready(replay_add(cfg, state, batch)["store"]["ptr"])
    t_add = _time(add, reps)

    key = jax.random.key(3)

    def sample():
        _, idx, _ = replay_sample(cfg, state, key, BATCH)
        jax.block_until_ready(idx)
    t_sample = _time(sample, reps)

    _, idx, _ = replay_sample(cfg, state, key, BATCH)
    pr = jnp.abs(jax.random.normal(jax.random.key(4), (BATCH,)))

    def upd():
        jax.block_until_ready(replay_update(cfg, state, idx, pr)["tree"])
    t_upd = _time(upd, reps)
    return t_add, t_sample, t_upd


def run(scale: str = "quick"):
    caps = [2 ** 14, 2 ** 16] if scale == "quick" \
        else [2 ** p for p in range(14, 21, 2)]
    reps = 5 if scale == "quick" else 20
    rows = []

    def emit(tag, cap, t_add, t_sample, t_upd):
        rows.append({"name": f"replay_sample_{tag}_c{cap}",
                     "us_per_call": t_sample,
                     "derived": f"{BATCH / (t_sample * 1e-6):.0f}_samples/s",
                     "add_us": round(t_add), "update_us": round(t_upd)})

    for cap in caps:
        emit("device", cap, *_bench_device(cap, reps, "xla"))
    # Pallas interpret mode: smallest capacity only (correctness harness)
    emit("device_pallas", caps[0], *_bench_device(caps[0], max(reps // 5, 1),
                                                  "pallas"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows
    print_rows(run())
