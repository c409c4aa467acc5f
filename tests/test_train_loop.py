"""Scan-superstep training loop tests: seed-for-seed parity between
``execution.loop="scan"`` and the per-step Python loop for BOTH replay
backends, the host-dispatch bound, n-step return emission against a NumPy
reference, the priority-staleness metric, the jitted eval rollout, the
superstep's phase scopes, and the 4-fake-device mesh-sharded runner
(subprocess, like test_substrate)."""
import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.replay import nstep_init, nstep_push_seq
from repro.rl import Experiment, ExperimentSpec, make_env
from repro.rl.envs import eval_returns, rollout_return

_BASE = dict(env="pendulum", algo="sac", num_units=16, num_layers=1,
             use_ofenet=False, distributed=True, n_core=1, n_env=4,
             total_steps=12, warmup_steps=8, eval_every=6, eval_episodes=1,
             replay_capacity=256, batch_size=16)


def _run(**overrides):
    """One-shot run via the Experiment handle (flat keys = spec aliases)."""
    spec = ExperimentSpec().override(**overrides)
    return Experiment.from_spec(spec).run(eval_at_end=True, keep_last=True)


# ------------------------------------------------------- scan/python parity

@pytest.mark.parametrize("backend,n_step", [("device", 1), ("device", 3),
                                            ("host", 1), ("host", 3)])
def test_scan_matches_python_loop(backend, n_step):
    """Same spec => identical returns and final priorities across loop
    drivers, for the device replay and the host (io_callback) replay."""
    cfg = dict(_BASE, replay_backend=backend, n_step=n_step)
    r_py = _run(**cfg, loop="python")
    r_sc = _run(**cfg, loop="scan")
    np.testing.assert_allclose(r_sc.returns, r_py.returns, rtol=1e-4)
    np.testing.assert_allclose(r_sc.last_priorities, r_py.last_priorities,
                               rtol=1e-3, atol=1e-5)
    assert r_sc.eval_steps == r_py.eval_steps == [6, 12]
    # the traced-call counter: scan dispatches one chunk per eval point
    # (+ O(1) warmup/init), the python loop ~5 programs per gradient step
    budget = _BASE["total_steps"] / _BASE["eval_every"] + 8
    assert r_sc.metrics["host_dispatches"] <= budget, r_sc.metrics
    assert r_py.metrics["host_dispatches"] > r_sc.metrics["host_dispatches"]


def test_scan_matches_python_loop_sranks():
    """srank instrumentation points must agree across loop drivers even when
    srank_every does not divide eval_every (scan chunks stop at both)."""
    cfg = dict(_BASE, replay_backend="device", srank_every=4)
    r_py = _run(**cfg, loop="python")
    r_sc = _run(**cfg, loop="scan")
    assert len(r_py.sranks) == len(r_sc.sranks) == 3
    assert r_py.sranks == r_sc.sranks
    np.testing.assert_allclose(r_sc.returns, r_py.returns, rtol=1e-4)


def test_scan_superstep_fused_block_backend_matches_jnp(monkeypatch):
    """block_backend="fused" routes every MLP block through the streaming
    stack kernel inside the scanned superstep, seed-for-seed with jnp (the
    fused path is float32-reassociation-identical at this scale)."""
    from repro.kernels.dense_block import stack as stack_mod
    calls = {"n": 0}
    inner = stack_mod.dense_stack

    def counted(*a, **k):
        calls["n"] += 1
        return inner(*a, **k)
    monkeypatch.setattr(stack_mod, "dense_stack", counted)

    cfg = dict(_BASE, replay_backend="device", use_ofenet=True,
               ofenet_layers=2, ofenet_units=16, loop="scan")
    r_jnp = _run(**cfg, block_backend="jnp")
    assert calls["n"] == 0                     # jnp backend never routes here
    r_fused = _run(**cfg, block_backend="fused")
    assert calls["n"] > 0                      # fused path actually traced
    np.testing.assert_allclose(r_fused.returns, r_jnp.returns, rtol=1e-3)
    np.testing.assert_allclose(r_fused.last_priorities, r_jnp.last_priorities,
                               rtol=5e-3, atol=1e-4)
    assert r_fused.eval_steps == r_jnp.eval_steps


def test_scan_matches_python_loop_pallas_kernel():
    """Loop driver parity must hold through the Pallas sum-tree too."""
    cfg = dict(_BASE, total_steps=6, eval_every=6, replay_capacity=128,
               replay_backend="device", replay_kernel="pallas")
    r_py = _run(**cfg, loop="python")
    r_sc = _run(**cfg, loop="scan")
    np.testing.assert_allclose(r_sc.returns, r_py.returns, rtol=1e-4)


# ----------------------------------------------------------- n-step returns

def _ref_nstep(n, gamma, trs):
    """Naive per-actor NumPy n-step roll-up (the host-path oracle)."""
    S, A = trs["rew"].shape
    out = {k: [] for k in ("obs", "act", "rew", "next_obs", "done", "disc")}
    for b in range(S - n + 1):
        row = {k: [] for k in out}
        for a in range(A):
            span = n
            for j in range(n):
                if trs["boundary"][b + j, a] > 0:
                    span = j + 1
                    break
            last = b + span - 1
            row["obs"].append(trs["obs"][b, a])
            row["act"].append(trs["act"][b, a])
            row["rew"].append(sum(gamma ** j * trs["rew"][b + j, a]
                                  for j in range(span)))
            row["next_obs"].append(trs["next_obs"][last, a])
            row["done"].append(trs["done"][last, a])
            row["disc"].append(gamma ** span * (1.0 - trs["done"][last, a]))
        for k in out:
            out[k].append(np.stack(row[k]))
    return {k: np.stack(v) for k, v in out.items()}


def test_nstep_emission_matches_numpy_reference():
    n, gamma, S, A = 3, 0.97, 12, 5
    rng = np.random.default_rng(0)
    trs = {"obs": rng.normal(size=(S, A, 2)).astype(np.float32),
           "act": rng.normal(size=(S, A, 1)).astype(np.float32),
           "rew": rng.normal(size=(S, A)).astype(np.float32),
           "next_obs": rng.normal(size=(S, A, 2)).astype(np.float32),
           "done": (rng.random((S, A)) < 0.2).astype(np.float32),
           "boundary": np.zeros((S, A), np.float32)}
    # boundaries wherever done, plus extra timeout-style cuts (done stays 0)
    trs["boundary"] = np.maximum(trs["done"],
                                 (rng.random((S, A)) < 0.25).astype(
                                     np.float32))
    buf = nstep_init(n, A, 2, 1)
    _, emitted = nstep_push_seq(n, gamma,
                                buf, {k: jnp.asarray(v)
                                      for k, v in trs.items()})
    ref = _ref_nstep(n, gamma, trs)
    for k, v in ref.items():
        np.testing.assert_allclose(np.asarray(emitted[k])[n - 1:], v,
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_nstep_one_is_identity_semantics():
    """n_step=1 keeps the legacy transition schema (no disc column)."""
    res = _run(**dict(_BASE, total_steps=4, eval_every=4,
                      replay_backend="device", n_step=1))
    assert "disc" not in res.last_batch


# ------------------------------------------------------- staleness metric

def test_staleness_metric_tracks_add_age():
    cfg = dict(_BASE, replay_backend="device", total_steps=30, eval_every=30)
    res = _run(**cfg, loop="scan")
    # sampled rows were added between warmup (step 0) and the last step
    assert 0.0 <= res.metrics["staleness_mean"] <= cfg["total_steps"]
    assert res.metrics["staleness_p50"] <= res.metrics["staleness_max"]
    assert res.metrics["staleness_max"] <= cfg["total_steps"]
    # host buffer does not stamp rows: staleness keys omitted (no sentinel)
    res_h = _run(**dict(cfg, replay_backend="host"))
    assert not any(k.startswith("staleness") for k in res_h.metrics)


# ------------------------------------------------------------ jitted eval

def test_eval_returns_matches_rollout_return():
    env = make_env("pendulum")

    def policy(params, obs):
        return jnp.tanh(obs[..., :env.act_dim] + params)

    # eval_returns consumes the policy duck-typed: anything without an
    # .act_deterministic is treated as a bare obs -> action callable
    def bound(o):
        return policy(jnp.float32(0.25), o[None])[0]

    key = jax.random.key(3)
    batched = eval_returns(env, bound, key, 3)
    legacy = [rollout_return(env, bound, jax.random.fold_in(key, i))
              for i in range(3)]
    np.testing.assert_allclose(np.asarray(batched), np.asarray(legacy),
                               rtol=1e-5)


# ------------------------------------------------------------ phase scopes

PHASES = ("repro.collect", "repro.replay.add", "repro.replay.sample",
          "repro.update", "repro.replay.refresh")


def _lowered_chunk(backend):
    exp = Experiment.from_spec(ExperimentSpec().override(
        **_BASE, replay_backend=backend, loop="scan"))
    exp._ensure_init()
    return exp.trainer.chunk_fn(3, False).__wrapped__.lower(exp._ls)


@pytest.mark.parametrize("backend", ["device", "host"])
def test_chunk_names_each_phase_and_adds_no_instruction(backend,
                                                        monkeypatch):
    """Each superstep phase runs under its own ``jax.named_scope``, which
    reaches the ops' metadata (the profiler's ``tf_op``) and nothing else:
    lowered again with the scopes turned off, the chunk is the same
    program once the printer leaves the locations out."""
    scoped = _lowered_chunk(backend)
    names = set(re.findall(r"repro\.[a-z.]+", scoped.as_text(debug_info=True)))
    assert set(PHASES) <= names
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _lowered_chunk(backend)
    assert not set(PHASES) & set(re.findall(
        r"repro\.[a-z.]+", plain.as_text(debug_info=True)))
    assert plain.as_text() == scoped.as_text()


# ------------------------------------------------------------ sharded smoke

_SHARDED_RUNNER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from repro.replay import sharded as shr

calls = {"collect_and_add_sharded": 0, "sharded_replay_sample": 0}
def _counted(name):
    inner = getattr(shr, name)
    def wrapped(*a, **k):
        calls[name] += 1
        return inner(*a, **k)
    return wrapped
for _name in calls:
    setattr(shr, _name, _counted(_name))

from repro.rl import Experiment, ExperimentSpec

def run(**kw):
    spec = ExperimentSpec().override(**kw)
    return Experiment.from_spec(spec).run(eval_at_end=True, keep_last=True)

base = dict(env="pendulum", algo="sac", num_units=16, num_layers=1,
            use_ofenet=False, distributed=True, n_core=1, n_env=8,
            total_steps=10, warmup_steps=16, eval_every=5, eval_episodes=2,
            replay_capacity=512, batch_size=16, replay_backend="device")
single = run(**base, loop="scan")
assert calls["collect_and_add_sharded"] == 0      # single shard: direct path
r_scan = run(**base, loop="scan", mesh_shards=4)
assert calls["collect_and_add_sharded"] > 0, calls
assert calls["sharded_replay_sample"] > 0, calls
assert r_scan.metrics["host_dispatches"] <= 10, r_scan.metrics
assert r_scan.metrics["staleness_mean"] >= 0
import warnings
with warnings.catch_warnings():
    warnings.simplefilter("ignore")               # python loop on a mesh
    r_py = run(**base, loop="python", mesh_shards=4)
np.testing.assert_allclose(r_scan.returns, r_py.returns, rtol=1e-4)
assert np.isfinite(r_scan.returns).all()
# same env/budget/seed: the sharded learning curve stays in the same
# ballpark as single-shard (pendulum random policy scores ~-1200)
assert abs(np.mean(r_scan.returns) - np.mean(single.returns)) < 400, (
    r_scan.returns, single.returns)
# n-step rides the sharded ring too
r_n3 = run(**base, loop="scan", mesh_shards=4, n_step=3)
assert np.isfinite(r_n3.returns).all()
print("OK")
"""


def test_sharded_runner_on_fake_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARDED_RUNNER],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


_MESH_TRACE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from repro.rl import ExperimentSpec
from repro.rl.runner import Trainer

spec = ExperimentSpec().override(
    num_units=16, num_layers=1, use_ofenet=False, n_core=1, n_env=8,
    batch_size=16, replay_capacity=512, warmup_steps=16,
    replay_backend="device", loop="scan", mesh_shards=4)
tr = Trainer(spec)
ls, metrics, _ = jax.eval_shape(tr._superstep, tr.init_template())
assert metrics["staleness_p50"].shape == ()
print("OK")
"""


def test_actor_mesh_keeps_sharded_superstep_traceable():
    """``make_actor_mesh`` must give axes the sharded superstep can trace
    under: with ``Explicit`` axes (``jax.make_mesh``'s default) the median
    of the data-sharded staleness vector refuses to trace."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _MESH_TRACE],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
