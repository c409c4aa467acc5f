"""Serving throughput/latency: continuous batching vs one-at-a-time.

Drives the ``repro.launch.serve_policy`` engine with CLOSED-LOOP clients
(each thread submits, waits for its action, submits again) at increasing
concurrency, against the one-request-at-a-time baseline (one client, ticks
of one — every request pays a full dispatch + demux round trip). With
``max_batch=B`` and 2B clients the queue always holds a full tick, so B
requests ride ONE jitted fused-stack forward on a padded batch slot — the
per-dispatch cost amortizes exactly like the trainer's chunked scan, and
the compile cache stays pinned to the slot set (no per-batch-size
recompiles; the engine pads to power-of-two slots).

Both legs run through the SAME server code path and their reps are
INTERLEAVED with min-of-reps taken, so the reported ratio is never an
artifact of when each leg was measured. The first pass of each leg
compiles + warms and is excluded. The hot-swap row pushes a new param
generation mid-traffic and asserts the engine's contract: zero dropped
responses, zero mixed generations, swap landed.

  PYTHONPATH=src python -m benchmarks.serve_policy
"""
from __future__ import annotations

import threading
import time

import numpy as np


def _policy():
    import jax
    from repro.rl import make_env, presets
    from repro.rl import sac as sac_mod
    from repro.rl.policy import Policy, algo_config

    spec = presets.get("smoke")
    env = make_env(spec.env)
    acfg = algo_config(spec, env)
    params = sac_mod.sac_init(jax.random.key(0), acfg)["params"]
    return Policy.from_spec(spec, params, env=env)


def _closed_loop_pass(pol, clients: int, max_batch: int, requests: int):
    """One timed pass: ``clients`` closed-loop threads push ``requests``
    total through a fresh server. Returns (wall_s, stats)."""
    from repro.launch.serve_policy import PolicyServer, ServeConfig

    server = PolicyServer(pol, ServeConfig(
        max_batch=max_batch, max_wait_ms=2.0,
        queue_size=max(1024, 2 * clients))).start()
    rng = np.random.default_rng(clients)
    obs = rng.standard_normal((clients, pol.obs_dim)).astype(np.float32)
    remaining = [requests]
    lock = threading.Lock()
    gate = threading.Barrier(clients + 1)         # exclude thread startup

    def client(cid):
        gate.wait()
        while True:
            with lock:
                if remaining[0] <= 0:
                    return
                remaining[0] -= 1
            server.submit(obs[cid], timeout=60.0)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    t0 = time.time()
    gate.wait()
    for t in threads:
        t.join()
    wall = time.time() - t0
    server.close()
    return wall, server.stats


def serve_throughput(batch: int, requests: int, reps: int):
    """Interleaved min-of-reps req/s for the serial (1 client, ticks of 1)
    and batched (2*batch clients, ticks of ``batch``) legs, plus the
    batched leg's best-rep latency percentiles."""
    pol = _policy()
    legs = {"serial": (1, 1), "batched": (2 * batch, batch)}
    for clients, mb in legs.values():             # compile + warm the slots
        _closed_loop_pass(pol, clients, mb, max(clients * 2, 8))
    best = {leg: float("inf") for leg in legs}
    lat = {}
    for _ in range(reps):
        for leg, (clients, mb) in legs.items():
            wall, stats = _closed_loop_pass(pol, clients, mb, requests)
            if wall < best[leg]:
                best[leg] = wall
                lat[leg] = stats["latencies_ms"]
    return ({leg: requests / b for leg, b in best.items()}, lat)


def hot_swap_under_load(pol, requests: int = 128):
    """Swap params mid-traffic; return (served, dropped, mixed, swaps).
    The engine contract says dropped == mixed == 0 and swaps == 1."""
    import jax
    from repro.launch.serve_policy import PolicyServer, ServeConfig

    gens = {0: pol, 1: pol.with_params(jax.tree_util.tree_map(
        lambda x: x + 0.25, pol.params))}
    server = PolicyServer(pol, ServeConfig(max_batch=8)).start()
    rng = np.random.default_rng(1)
    obs = rng.standard_normal((requests, pol.obs_dim)).astype(np.float32)
    results = [None] * requests

    def client(lo, hi):
        for i in range(lo, hi):
            t = server.submit_async(obs[i])
            results[i] = (t.result(timeout=60.0), t.generation)

    n = 4
    threads = [threading.Thread(target=client,
                                args=(j * requests // n,
                                      (j + 1) * requests // n))
               for j in range(n)]
    for t in threads:
        t.start()
    time.sleep(0.005)
    server.push_params(gens[1].params)
    for t in threads:
        t.join()
    server.close()

    dropped = sum(r is None or r[0] is None for r in results)
    mixed = 0
    for i, r in enumerate(results):
        if r is None or r[0] is None:
            continue
        action, g = r
        want = np.asarray(gens[g].act_deterministic(obs[i]))
        if not np.allclose(action, want, rtol=1e-5, atol=1e-6):
            mixed += 1
    return requests - dropped, dropped, mixed, server.stats["swaps"]


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def run(scale: str = "quick"):
    requests = {"smoke": 96, "quick": 512}.get(scale, 2048)
    reps = 1 if scale == "smoke" else 5
    rows = []
    serial_sps = None
    for batch in (8, 32):
        sps, lat = serve_throughput(batch, requests, reps)
        if serial_sps is None:                    # one serial baseline row
            serial_sps = sps["serial"]
            s_lat = lat["serial"]
            rows.append({"name": "serve_policy_serial",
                         "us_per_call": 1e6 / serial_sps,
                         "derived": f"{serial_sps:.0f}_req/s",
                         "p50_ms": round(_pct(s_lat, 50), 3),
                         "p99_ms": round(_pct(s_lat, 99), 3),
                         "requests": requests, "reps": reps})
        ratio = sps["batched"] / serial_sps
        b_lat = lat["batched"]
        rows.append({"name": f"serve_policy_batch{batch}",
                     "us_per_call": 1e6 / sps["batched"],
                     "derived": f"{sps['batched']:.0f}_req/s_x{ratio:.1f}",
                     "ratio_vs_serial": round(ratio, 2),
                     "baseline_req_per_sec": round(serial_sps, 1),
                     "p50_ms": round(_pct(b_lat, 50), 3),
                     "p99_ms": round(_pct(b_lat, 99), 3),
                     "requests": requests, "reps": reps})
    served, dropped, mixed, swaps = hot_swap_under_load(_policy())
    if dropped or mixed or swaps != 1:
        raise AssertionError(f"hot-swap contract broken: dropped={dropped} "
                             f"mixed={mixed} swaps={swaps}")
    rows.append({"name": "serve_policy_hotswap",
                 "us_per_call": 0.0,
                 "derived": f"{served}_served_0_dropped_0_mixed",
                 "served": served, "dropped": dropped,
                 "mixed_generation": mixed, "swaps": swaps})
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows
    print_rows(run())
