"""Loop-fusion benchmark: gradient-steps/sec, loop="python" vs loop="scan".

The per-step Python loop dispatches ~5 host->device programs per gradient
step; the scanned superstep amortizes ONE dispatch over a whole
``eval_every`` chunk (see rl/runner.py). Both drivers run the identical
superstep math (device replay, SAC, pendulum), so the gap is pure dispatch/
transfer overhead — the quantity that bounds sweep throughput on CPU and
dispatch-latency-bound accelerators alike. The chunk carries its last
step's metrics/batch through the scan carry (the bitwise resume-anywhere
contract, PR 5); these rows confirm the carried outputs do not regress the
dispatch-amortization win.

The obs rows measure the telemetry tax on the scan driver: obs off vs the
default ``obs.log_every=50`` stream vs a pathological per-step stream
(``log_every=1``). The stream is emitted as stacked scan outputs and
downsampled on the host, so the cost is one extra device->host fetch per
chunk — the acceptance bar is < 5% at the default cadence
(experiments/bench_results.json).

Timed via ``rl.runner.Trainer`` directly (warm call first, so compile time
is excluded). ``--mesh N`` adds the mesh legs (``execution.mesh_shards=N``,
routed through ``collect_and_add_sharded`` / ``sharded_replay_sample``) in
this same process, on the devices it already holds: the chips on an
accelerator host, or fake CPU devices when the caller sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before starting it.
Every row names the backend it ran on (``platform``); a CPU row is a CPU
measurement, whatever its leg.

  PYTHONPATH=src python -m benchmarks.loop_fusion [--mesh 4]
"""
import time


def _spec(loop, steps, mesh_shards=0, **obs):
    from repro.rl import ExperimentSpec
    kw = {"obs." + k: v for k, v in obs.items()}
    return ExperimentSpec().override(
        env="pendulum", algo="sac", num_units=32, num_layers=1,
        use_ofenet=False, distributed=True, n_core=1, n_env=16,
        total_steps=steps, warmup_steps=64, eval_every=steps,
        batch_size=64, replay_capacity=4096,
        replay_backend="device", loop=loop, mesh_shards=mesh_shards, **kw)


def _timed_pass(trainer, loop: str, steps: int):
    """One warm Trainer + a closure timing one full ``steps``-long pass.
    When the trainer's spec has obs enabled, the timed region includes the
    obs host path (stream fetch + absolute-step downsample into a memory
    sink), like ``Experiment.run``'s."""
    import jax
    from repro.obs.stream import ObsRun
    obs = ObsRun(trainer.spec.obs)
    ls = trainer.init()
    if loop == "scan":
        chunk = trainer.chunk_fn(steps, False)
        ls, _ = chunk(ls)                       # compile + warm
        jax.block_until_ready(ls.agent["params"])
        state = {"ls": ls, "step": 0}

        def one():
            t0 = time.time()
            state["ls"], out = chunk(state["ls"])
            if "stream" in out:
                obs.flush_chunk(state["step"],
                                jax.device_get(out["stream"]))
                state["step"] += steps
            jax.block_until_ready(state["ls"].agent["params"])
            return time.time() - t0
        return one
    ls, _, _ = trainer.py_step(ls)              # compile + warm
    jax.block_until_ready(ls.agent["params"])
    state = {"ls": ls}

    def one():
        t0 = time.time()
        for _ in range(steps):
            state["ls"], _, _ = trainer.py_step(state["ls"])
        jax.block_until_ready(state["ls"].agent["params"])
        return time.time() - t0
    return one


def steps_per_sec(loop: str, steps: int, mesh_shards: int = 0,
                  reps: int = 3) -> float:
    """Steady-state gradient steps/sec: best of ``reps`` timed passes after
    a warm call (compile excluded; min-of-reps rejects scheduler noise the
    way benchmarks/dense_stack.py does)."""
    from repro.rl.runner import Trainer
    one = _timed_pass(Trainer(_spec(loop, steps, mesh_shards)), loop, steps)
    return steps / min(one() for _ in range(reps))


def both_steps_per_sec(steps: int, mesh_shards: int = 0,
                       reps: int = 5) -> dict:
    """python AND scan steps/sec with the timed reps INTERLEAVED, so both
    drivers sample the same host-load environment and the reported ratio
    is not an artifact of when each driver happened to be measured."""
    from repro.rl.runner import Trainer
    ones = {loop: _timed_pass(Trainer(_spec(loop, steps, mesh_shards)),
                              loop, steps)
            for loop in ("python", "scan")}
    best = {loop: float("inf") for loop in ones}
    for _ in range(reps):
        for loop, one in ones.items():
            best[loop] = min(best[loop], one())
    return {loop: steps / b for loop, b in best.items()}


def obs_overhead_steps_per_sec(steps: int, reps: int = 5) -> dict:
    """Scan-driver steps/sec with telemetry off / default / per-step, reps
    interleaved like ``both_steps_per_sec``. Keys: "off", "every50",
    "every1"."""
    from repro.rl.runner import Trainer
    variants = {
        "off": {},
        "every50": dict(enabled=True, log_every=50, grad_norms=True),
        "every1": dict(enabled=True, log_every=1, grad_norms=True),
    }
    ones = {}
    for tag, obs in variants.items():
        spec = _spec("scan", steps, **obs)
        ones[tag] = _timed_pass(Trainer(spec), "scan", steps)
    best = {tag: float("inf") for tag in ones}
    for _ in range(reps):
        for tag, one in ones.items():
            best[tag] = min(best[tag], one())
    return {tag: steps / b for tag, b in best.items()}


def run(scale: str = "quick", mesh: int = 0):
    import jax
    steps = {"smoke": 16, "quick": 64}.get(scale, 512)
    mesh_steps = 192 if scale == "quick" else 1024
    platform = jax.default_backend()
    rows = []

    def emit(tag, sps, ratio=None):
        derived = f"{sps:.0f}_steps/s" + (f"_x{ratio:.1f}" if ratio else "")
        rows.append({"name": f"loop_fusion_{tag}", "us_per_call": 1e6 / sps,
                     "derived": derived, "platform": platform})

    if scale == "smoke":      # CI bitrot guard: one rep, no mesh legs
        sps_py = steps_per_sec("python", steps, reps=1)
        sps_sc = steps_per_sec("scan", steps, reps=1)
        emit("python_1shard", sps_py)
        emit("scan_1shard", sps_sc, sps_sc / sps_py)
        return rows
    sps = both_steps_per_sec(steps)
    sps_py, sps_sc = sps["python"], sps["scan"]
    emit("python_1shard", sps_py)
    emit("scan_1shard", sps_sc, sps_sc / sps_py)
    # the telemetry tax is a few ms of host work per CHUNK, so resolving
    # it needs passes much longer than the python-vs-scan comparison
    # (64-step passes are ~15ms and drown the signal in scheduler noise)
    obs = obs_overhead_steps_per_sec(512 if scale == "quick" else 2048)
    emit("obs_off", obs["off"])
    # ratio here = throughput retained with the stream on (1.00 = free)
    emit("obs_every50", obs["every50"], obs["every50"] / obs["off"])
    emit("obs_every1", obs["every1"], obs["every1"] / obs["off"])
    if mesh:
        sps = both_steps_per_sec(mesh_steps, mesh_shards=mesh, reps=3)
        emit(f"python_mesh{mesh}", sps["python"])
        emit(f"scan_mesh{mesh}", sps["scan"], sps["scan"] / sps["python"])
    return rows


if __name__ == "__main__":
    import argparse

    from benchmarks.common import print_rows
    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", type=int, default=0,
                    help="also time the mesh legs on this many devices")
    args = ap.parse_args()
    enable_compile_cache()
    print_rows(run(mesh=args.mesh))
