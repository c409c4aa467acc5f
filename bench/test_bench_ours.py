"""The paper's agent against its plain reference (``reference/ours_ref.py``)
on the CPU at a small size: the quadruped env over a rollout, one SAC
update with OFENet's batch norm from seeded weights, and the scopes its
per-layer metrics read (in the lowered update, by ``scope_seconds``, by
the two readers)."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from reference import ours_ref, sac_ref

KIND = harness.kind_module("train_window_ours")
CELL = "ours-u2048-train"


def _small_cfg():
    cfg = json.loads(json.dumps(harness.cell(CELL)["config"]))
    cfg["spec"]["network"]["num_units"] = 32
    cfg["spec"]["ofenet"].update(num_units=8, num_layers=2)
    return cfg


def _acfg(cfg):
    from repro.rl.envs import make_env
    from repro.rl.experiment import ExperimentSpec
    from repro.rl.policy import algo_config
    spec = ExperimentSpec.from_dict(cfg["spec"])
    return algo_config(spec, make_env(spec.env))


def test_quadruped_matches_the_reference_over_a_rollout():
    from repro.rl.envs import make_env
    env = make_env("quadruped")
    assert (env.obs_dim, env.act_dim, env.max_episode_steps) == (111, 8, 1000)
    keys = jax.random.split(jax.random.key(3), 4)
    acts = jax.random.uniform(jax.random.key(4), (60, 4, 8), minval=-1.2,
                              maxval=1.2)

    @jax.jit
    def program(keys, acts):
        s = jax.vmap(env.reset)(keys)

        def one(s, a):
            s2, obs, r, done = jax.vmap(env.step)(s, a)
            return s2, (jax.vmap(env.obs)(s), obs, r, done, s2.q, s2.qd)
        return jax.lax.scan(one, s, acts)[1]

    @jax.jit
    def reference(keys, acts):
        s = jax.vmap(ours_ref.quad_reset)(keys)

        def one(s, a):
            s2, r = jax.vmap(ours_ref.quad_step)(s, a)
            return s2, (jax.vmap(ours_ref.quad_obs)(s),
                        jax.vmap(ours_ref.quad_obs)(s2), r, s2["q"],
                        s2["qd"])
        return jax.lax.scan(one, s, acts)[1]

    obs, obs2, rew, done, q, qd = program(keys, acts)
    r_obs, r_obs2, r_rew, r_q, r_qd = reference(keys, acts)
    for a, b in ((obs, r_obs), (obs2, r_obs2), (rew, r_rew), (q, r_q),
                 (qd, r_qd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    assert not np.asarray(done).any()
    # the feet touch the ground: the contact rows are read, and clipped
    cfrc = np.asarray(obs)[..., 27:]
    assert np.abs(cfrc).max() == 1.0 and (np.abs(cfrc) > 0).any()


def test_one_update_with_batch_norm_matches_the_reference():
    from repro.optim import adamw_init
    from repro.rl.sac import sac_update
    cfg = _small_cfg()
    acfg = _acfg(cfg)
    params = KIND.make_params(harness.params_template(cfg), 0.1,
                              jax.random.key(5))
    k = jax.random.split(jax.random.key(6), 6)
    n = 32
    batch = {"obs": jax.random.normal(k[0], (n, 111)),
             "act": jax.random.uniform(k[1], (n, 8), minval=-1, maxval=1),
             "rew": jax.random.normal(k[2], (n,)),
             "next_obs": jax.random.normal(k[3], (n, 111)),
             "done": jnp.zeros((n,)),
             "weight": jax.random.uniform(k[4], (n,), minval=0.1)}
    state = {"params": params, "step": jnp.zeros((), jnp.int32),
             "opt": {name: adamw_init(t)
                     for name, t in sac_ref.trained(params).items()}}
    nets = ours_ref.Nets(cfg)
    with jax.default_matmul_precision("highest"):
        new, m = jax.jit(lambda s, b, kk: sac_update(s, acfg, b, kk))(
            state, batch, k[5])
        rp, ro, rm, rg = jax.jit(lambda p, b, kk: ours_ref.sac_step(
            nets, p, sac_ref.opt_init(p), b, kk))(params, batch, k[5])
    # OFENet's auxiliary step comes first and matches closely: its loss,
    # its gradients (the program's first Adam moment holds (1 - b1) g) and
    # the running statistics it moved
    np.testing.assert_allclose(float(m["aux_loss"]), float(rm["aux_loss"]),
                               rtol=1e-6)
    close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    jax.tree_util.tree_map(lambda mu, g: close(mu / 0.1, g),
                           new["opt"]["ofenet"]["mu"], rg["ofenet"])
    for net in ("phi_s", "phi_sa"):
        for lp, lr_ in zip(new["params"]["ofenet"]["online"][net]["layers"],
                           rp["ofenet"]["online"][net]["layers"]):
            for stat in ("mean", "var"):
                close(lp["bn"][stat], lr_["bn"][stat])
    # A dense bias that batch norm follows has a zero gradient up to
    # rounding, which that Adam step turns into a step of up to lr of
    # either sign: the features the SAC losses read carry that much noise,
    # so those are compared as the benchmark compares them (leaf norms)
    for name in ("critic_loss", "actor_loss"):
        np.testing.assert_allclose(float(m[name]), float(rm[name]),
                                   rtol=1e-3, err_msg=name)
    tw = KIND.tw
    f = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
    grads = {name: jax.tree_util.tree_map(lambda mu: mu / 0.1,
                                          new["opt"][name]["mu"])
             for name in rg}
    gref = f(tw._leaf_norms(rg))
    assert tw._norm_gap(f(tw._leaf_norms(grads)), gref, gref) < 1e-3
    assert tw._norm_gap(f(tw._delta_norms(new["params"], params)),
                        f(tw._delta_norms(rp, params)), gref) < 1e-3
    online0, online1 = (p["ofenet"]["online"] for p in (params,
                                                        new["params"]))
    bn0 = online0["phi_sa"]["layers"][0]["bn"]
    bn1 = online1["phi_sa"]["layers"][0]["bn"]
    assert not np.allclose(np.asarray(bn0["mean"]), np.asarray(bn1["mean"]))
    assert not np.allclose(np.asarray(bn0["var"]), np.asarray(bn1["var"]))


def test_the_lowered_update_names_its_parts():
    from repro.rl.experiment import Experiment, ExperimentSpec
    cfg = _small_cfg()
    cfg["spec"]["replay"].update(capacity=256, kernel="xla")
    tr = Experiment.from_spec(ExperimentSpec.from_dict(cfg["spec"])).trainer
    ls, _ = jax.eval_shape(tr._fresh_state)
    batch = {"obs": (32, 111), "act": (32, 8), "rew": (32,),
             "next_obs": (32, 111), "done": (32,), "weight": (32,)}
    batch = {f: jax.ShapeDtypeStruct(s, jnp.float32)
             for f, s in batch.items()}
    text = jax.jit(tr._op_update).lower(
        ls.agent, batch, jax.random.key(0)).as_text(debug_info=True)
    for scope in ("repro.update/", "repro.update.critic",
                  "repro.update.actor", "repro.update.alpha",
                  "repro.ofenet"):
        assert scope in text, scope


def test_scope_seconds_reads_each_scope_at_any_depth(monkeypatch):
    import trace_scopes
    stacks = {
        "%f.1 = f32[] fusion()":
            "jit(chunk)/while/body/repro.update/repro.update.critic/"
            "transpose(jvp(repro.ofenet))/dot_general:",
        "%f.2 = f32[] fusion()":
            "jit(chunk)/while/body/repro.update/repro.update.actor/mul:",
        "%f.3 = f32[] fusion()":
            "jit(chunk)/while/body/repro.collect/repro.ofenet/dot_general:",
        "%f.4 = f32[] fusion()": "jit(chunk)/while/body/repro.update/add:",
        "%f.5 = f32[] fusion()": "jit(chunk)/while/body/repro.updates/x:",
    }
    monkeypatch.setattr(trace_scopes, "op_scopes",
                        lambda _: {"/device:TPU:0": stacks,
                                   "/device:TPU:1": stacks})
    ops = {"f.1": 1.0, "f.2": 2.0, "f.3": 4.0, "f.4": 8.0, "f.5": 16.0}
    red = {"devices": {"/device:TPU:0": {"ops": ops},
                       "/device:TPU:1": {"ops": {k: 3 * v for k, v in
                                                 ops.items()}}}}
    got = KIND.scope_seconds("unused", red)
    assert got == {"repro.update": 22.0, "repro.update.critic": 2.0,
                   "repro.update.actor": 4.0, "repro.update.alpha": 0.0,
                   "repro.ofenet": 10.0}


def test_readers_of_the_scopes():
    um = harness.load_module(harness.BENCH / "metrics" / "update_mfu.py",
                             "bench_metric_update_mfu")
    osh = harness.load_module(harness.BENCH / "metrics" / "ofenet_share.py",
                              "bench_metric_ofenet_share")
    import flops
    cfg = harness.cell(CELL)["config"]

    class Dev:
        device_kind = "TPU v5 lite"
    red = {"busy_s": 2.0, "window_s": 2.5,
           "scopes": {"repro.update": 1.5, "repro.ofenet": 0.5}}
    ctx = {"reduced": red, "run": {"traced_updates": 1000},
           "config": cfg, "devices": [Dev()]}
    want = 100 * flops.update_flops(cfg) * 1000 / (1.5 * 197e12)
    assert um.read(ctx) == pytest.approx(want)
    assert osh.read(ctx) == pytest.approx(25.0)
    # a trace without the scopes (a program that lacks them): no reading
    for r in ({k: v for k, v in red.items() if k != "scopes"},
              dict(red, scopes={"repro.update": 0.0, "repro.ofenet": 0.0})):
        ctx["reduced"] = r
        assert um.read(ctx) is None and osh.read(ctx) is None


def test_update_flops_match_xla_at_the_cells_widths():
    """``flops.update_flops`` against XLA's cost analysis of the jitted
    update at this configuration's env widths (111 / 8) and U=256: within
    5 % below, as ``test_bench_flops.py`` checks at pendulum's (3 / 1),
    the batch norm's elementwise work being among what XLA adds. A
    duplicate of that check: delete it once ``test_bench_flops.py`` takes
    each configuration's widths from its ``env_dims``."""
    import flops
    from repro.rl import sac
    cfg = _small_cfg()
    cfg["spec"]["network"]["num_units"] = 256
    cfg["spec"]["ofenet"].update(num_units=64, num_layers=4)
    acfg = _acfg(cfg)
    b = cfg["spec"]["execution"]["batch_size"]
    e = cfg["env_dims"]
    state = jax.eval_shape(lambda k: sac.sac_init(k, acfg), jax.random.key(0))
    shapes = {"obs": (b, e["obs"]), "act": (b, e["act"]), "rew": (b,),
              "next_obs": (b, e["obs"]), "done": (b,), "weight": (b,)}
    batch = {k: jax.ShapeDtypeStruct(s, jnp.float32)
             for k, s in shapes.items()}
    ca = jax.jit(lambda s, bt, k: sac.sac_update(s, acfg, bt, k)).lower(
        state, batch, jax.random.key(0)).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert 1.0 <= ca["flops"] / flops.update_flops(cfg) <= 1.05, ca["flops"]
