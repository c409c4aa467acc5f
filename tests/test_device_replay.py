"""Parity tests: repro.replay (device) vs rl.replay (host oracle), plus the
runner's ``replay_backend="device"`` end-to-end path and the mesh-sharded
variant (4 fake CPU devices, subprocess like test_substrate)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.replay_tree.ops import sumtree_get
from repro.replay import (DeviceReplay, DeviceReplayConfig, replay_add,
                          replay_init, replay_sample, replay_update,
                          store_add, store_gather, store_init)
from repro.rl.replay import PrioritizedReplay, UniformReplay


from _transitions import mk_batch as _mk_batch  # noqa: E402


# ------------------------------------------------------------------- store

def _assert_columns_match(st, host):
    assert set(st["data"]) == set(host.data)
    for k, col in host.data.items():
        np.testing.assert_array_equal(np.asarray(st["data"][k]), col,
                                      err_msg=k)


# (first, second) block sizes into capacity 8: the second block ends at the
# end, or starts at capacity - 2 and crosses it
@pytest.mark.parametrize("first,second", [(8, 4), (6, 5)])
@pytest.mark.parametrize("act_dim", [1, 2])
def test_store_wraparound_matches_host_layout(act_dim, first, second):
    st = store_init(8, 3, act_dim)
    b1 = _mk_batch(first, act_dim=act_dim, seed=1)
    b2 = _mk_batch(second, act_dim=act_dim, seed=2)
    st, _ = store_add(st, {k: jnp.asarray(v) for k, v in b1.items()})
    st, idx = store_add(st, {k: jnp.asarray(v) for k, v in b2.items()})
    host = PrioritizedReplay(8, 3, act_dim)
    host.add_batch(b1)
    host.add_batch(b2)
    _assert_columns_match(st, host)
    assert int(st["count"]) == len(host) == min(first + second, 8)
    assert int(st["ptr"]) == host.ptr
    np.testing.assert_array_equal(np.asarray(idx),
                                  (first + np.arange(second)) % 8)
    got = store_gather(st, jnp.asarray([0, 5]))
    for k, col in host.data.items():
        np.testing.assert_array_equal(np.asarray(got[k]), col[[0, 5]],
                                      err_msg=k)


@pytest.mark.parametrize("act_dim", [1, 2])
def test_store_add_larger_than_capacity_matches_host(act_dim):
    """A batch that laps the buffer keeps the last writes, like the host."""
    st = store_init(8, 3, act_dim)
    big = _mk_batch(20, act_dim=act_dim, seed=20)
    st, idx = store_add(st, {k: jnp.asarray(v) for k, v in big.items()})
    host = PrioritizedReplay(8, 3, act_dim)
    host.add_batch(big)
    _assert_columns_match(st, host)
    assert int(st["count"]) == 8 and int(st["ptr"]) == 20 % 8 == host.ptr
    assert idx.shape == (8,)
    # priorities passed alongside an oversized batch stay row-aligned
    cfg = DeviceReplayConfig(capacity=8, obs_dim=3, act_dim=act_dim,
                             alpha=1.0)
    pr = np.arange(1.0, 21.0, dtype=np.float32)
    state = replay_add(cfg, replay_init(cfg),
                       {k: jnp.asarray(v) for k, v in big.items()},
                       jnp.asarray(pr))
    leaves = np.asarray(sumtree_get(state["tree"], jnp.arange(8)))
    hostp = PrioritizedReplay(8, 3, act_dim, alpha=1.0)
    hostp.add_batch(big, pr)
    np.testing.assert_allclose(leaves, hostp.tree.get(np.arange(8)),
                               rtol=1e-5)


# ------------------------------------------------------- prioritized parity

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_priorities_roundtrip_matches_host(backend):
    """add + update_priorities leave identical leaf masses in both trees."""
    cfg = DeviceReplayConfig(capacity=64, obs_dim=3, act_dim=2,
                             backend=backend)
    dev, host = DeviceReplay(cfg), PrioritizedReplay(64, 3, 2)
    b = _mk_batch(40, seed=3)
    dev.add_batch(b)
    host.add_batch(b)
    np.testing.assert_allclose(dev.total, host.tree.total, rtol=1e-5)
    pr = np.abs(np.random.default_rng(4).normal(size=40)).astype(np.float32)
    dev.update_priorities(np.arange(40), pr)
    host.update_priorities(np.arange(40), pr)
    dev_leaves = np.asarray(sumtree_get(dev.state["tree"], jnp.arange(40)))
    host_leaves = host.tree.get(np.arange(40))
    np.testing.assert_allclose(dev_leaves, host_leaves, rtol=1e-5)
    np.testing.assert_allclose(dev.total, host.tree.total, rtol=1e-5)


def test_sampled_index_distribution_matches_host():
    """Same priorities => empirical sample frequencies agree within tol."""
    capacity, n, draws = 128, 100, 40_000
    cfg = DeviceReplayConfig(capacity=capacity, obs_dim=3, act_dim=2,
                             alpha=1.0)
    dev = DeviceReplay(cfg)
    host = PrioritizedReplay(capacity, 3, 2, alpha=1.0)
    b = _mk_batch(n, seed=5)
    pr = np.random.default_rng(6).uniform(0.1, 5.0, n).astype(np.float32)
    dev.add_batch(b)
    host.add_batch(b)
    dev.update_priorities(np.arange(n), pr)
    host.update_priorities(np.arange(n), pr)

    rng = np.random.default_rng(7)
    host_counts = np.zeros(n)
    dev_counts = np.zeros(n)
    key = jax.random.key(8)
    for i in range(draws // 400):
        _, hidx, _ = host.sample(400, rng)
        host_counts += np.bincount(hidx, minlength=n)[:n]
        key, k = jax.random.split(key)
        _, didx, _ = dev.sample(400, k)
        dev_counts += np.bincount(np.asarray(didx), minlength=n)[:n]
    expected = pr / pr.sum()
    np.testing.assert_allclose(host_counts / draws, expected, atol=0.01)
    np.testing.assert_allclose(dev_counts / draws, expected, atol=0.01)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_is_weights_match_host_formula(backend):
    cfg = DeviceReplayConfig(capacity=64, obs_dim=3, act_dim=2,
                             backend=backend)
    dev = DeviceReplay(cfg)
    dev.add_batch(_mk_batch(50, seed=9))
    pr = np.random.default_rng(10).uniform(0.1, 3.0, 50).astype(np.float32)
    dev.update_priorities(np.arange(50), pr)
    _, idx, w = dev.sample(32, jax.random.key(11))
    idx, w = np.asarray(idx), np.asarray(w)
    leaf = np.asarray(sumtree_get(dev.state["tree"], jnp.asarray(idx)))
    p = leaf / dev.total
    ref_w = (50 * np.maximum(p, 1e-12)) ** (-cfg.beta)
    ref_w /= ref_w.max()
    np.testing.assert_allclose(w, ref_w, rtol=1e-4)
    assert (w > 0).all() and (w <= 1.0 + 1e-6).all()


def test_device_sample_is_deterministic_per_key():
    cfg = DeviceReplayConfig(capacity=32, obs_dim=3, act_dim=2)
    dev = DeviceReplay(cfg)
    dev.add_batch(_mk_batch(32, seed=12))
    _, i1, _ = dev.sample(16, jax.random.key(13))
    _, i2, _ = dev.sample(16, jax.random.key(13))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_device_prioritized_focuses_high_td():
    cfg = DeviceReplayConfig(capacity=100, obs_dim=3, act_dim=2, alpha=1.0)
    dev = DeviceReplay(cfg)
    dev.add_batch(_mk_batch(100, seed=14))
    pr = np.full(100, 1e-3, np.float32)
    pr[7] = 10.0
    dev.update_priorities(np.arange(100), pr)
    key, hits = jax.random.key(15), 0
    for _ in range(50):
        key, k = jax.random.split(key)
        _, idx, _ = dev.sample(16, k)
        hits += int((np.asarray(idx) == 7).sum())
    assert hits > 200


# ----------------------------------------------------------- uniform parity

def test_uniform_parity_with_host():
    cfg = DeviceReplayConfig(capacity=64, obs_dim=3, act_dim=2, uniform=True)
    dev, host = DeviceReplay(cfg), UniformReplay(64, 3, 2)
    b = _mk_batch(64, seed=16)
    dev.add_batch(b)
    host.add_batch(b)
    _, idx, w = dev.sample(32, jax.random.key(17))
    assert (np.asarray(w) == 1.0).all()
    assert np.asarray(idx).min() >= 0 and np.asarray(idx).max() < 64
    # update_priorities is a no-op, as on the host
    state = replay_update(cfg, dev.state, idx, jnp.ones((32,)))
    np.testing.assert_array_equal(np.asarray(state["tree"]),
                                  np.asarray(dev.state["tree"]))


# -------------------------------------------------------- functional API jit

def test_functional_loop_is_jittable_end_to_end():
    """add -> sample -> update as one jitted program (the runner's shape)."""
    cfg = DeviceReplayConfig(capacity=32, obs_dim=3, act_dim=2)

    @jax.jit
    def one_step(state, batch, key):
        state = replay_add(cfg, state, batch)
        out, idx, w = replay_sample(cfg, state, key, 8)
        state = replay_update(cfg, state, idx, jnp.abs(out["rew"]) + 0.1)
        return state, idx, w

    state = replay_init(cfg)
    batch = {k: jnp.asarray(v) for k, v in _mk_batch(16, seed=18).items()}
    state, idx, w = one_step(state, batch, jax.random.key(19))
    assert int(state["store"]["count"]) == 16
    assert np.isfinite(np.asarray(w)).all()
    assert np.asarray(idx).max() < 16


# ------------------------------------------------------------------- runner

@pytest.mark.parametrize("algo", ["sac", "td3"])
def test_runner_device_backend_trains(algo):
    from repro.rl import Experiment, ExperimentSpec
    spec = ExperimentSpec().override(
        env="pendulum", algo=algo, num_units=16, num_layers=1,
        use_ofenet=False, distributed=True, n_core=1, n_env=4,
        total_steps=10, warmup_steps=8, eval_every=10,
        eval_episodes=1, replay_capacity=512, batch_size=16,
        replay_backend="device")
    res = Experiment.from_spec(spec).run(eval_at_end=True)
    assert len(res.returns) == 1 and np.isfinite(res.returns[0])


def test_runner_device_pallas_matches_xla():
    """The kernel choice must not change the training trajectory."""
    from repro.rl import Experiment, ExperimentSpec
    base = dict(env="pendulum", num_units=16, num_layers=1, use_ofenet=False,
                distributed=True, n_core=1, n_env=4, total_steps=8,
                warmup_steps=8, eval_every=8, eval_episodes=1,
                replay_capacity=256, batch_size=16, replay_backend="device")

    def run(**kw):
        spec = ExperimentSpec().override(**base, **kw)
        return Experiment.from_spec(spec).run(eval_at_end=True)

    r_xla = run(replay_kernel="xla")
    r_pal = run(replay_kernel="pallas")
    np.testing.assert_allclose(r_xla.returns, r_pal.returns, rtol=1e-4)


# ------------------------------------------------------------------ sharded

_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_debug_mesh, replay_shards
mesh = make_debug_mesh(4, 1)
assert replay_shards(mesh) == 4
from repro.replay import (DeviceReplayConfig, collect_and_add_sharded,
                          sharded_replay_init, sharded_replay_sample,
                          sharded_replay_update)
from repro.rl import apex, make_env

env = make_env("pendulum")
cfg = DeviceReplayConfig(capacity=32, obs_dim=env.obs_dim,
                         act_dim=env.act_dim)
st = sharded_replay_init(cfg, mesh)
states = apex.init_actor_states(env, jax.random.key(0), 8)
rand = apex.random_policy(env.act_dim)
states, st = collect_and_add_sharded(env, rand, mesh, cfg, {}, states, 3,
                                     jax.random.key(1), st)
assert (np.asarray(st["store"]["count"]) == 6).all(), st["store"]["count"]
batch, idx, w = sharded_replay_sample(cfg, mesh, st, jax.random.key(2), 16)
assert batch["obs"].shape == (16, env.obs_dim)
assert np.asarray(idx).min() >= 0 and np.asarray(idx).max() < 32
assert np.isfinite(np.asarray(w)).all() and float(np.max(np.asarray(w))) <= 1.0 + 1e-6
st = sharded_replay_update(cfg, mesh, st, idx,
                           jnp.abs(jax.random.normal(jax.random.key(3),
                                                     (16,))) + 0.1)
totals = np.asarray(st["tree"][:, 1])
assert (totals > 0).all()
# plain sharded actor pool still agrees with the fused path on shapes
states2, trs = apex.collect_sharded(env, rand, mesh, {}, states, 2,
                                    jax.random.key(4))
assert trs["obs"].shape == (16, env.obs_dim)
print("OK")
"""


def test_sharded_replay_on_fake_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARDED], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
