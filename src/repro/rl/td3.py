"""TD3 (Fujimoto et al. 2018) with the paper's architecture options.

Same connectivity/OFENet knobs as SAC (the paper evaluates both, Table 1).
Batch size 256 per paper A.4; Huber critic loss per A.1; delayed policy
updates every 2 critic steps; target policy smoothing.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import (Params, PRNGKey, ema_update, huber, split_keys,
                          tree_l2_norm, tree_update_ratio)
from repro.core.blocks import MLPBlockConfig, mlp_block_apply, mlp_block_init
from repro.core.ofenet import OFENetConfig
from repro.core import ofenet as ofe
from repro.optim import AdamWConfig, adamw_init, adamw_update


@dataclasses.dataclass(frozen=True)
class TD3Config:
    obs_dim: int
    act_dim: int
    num_units: int = 256
    num_layers: int = 2
    connectivity: str = "densenet"
    activation: str = "swish"
    gamma: float = 0.99
    tau: float = 0.005
    lr: float = 3e-4
    policy_noise: float = 0.2
    noise_clip: float = 0.5
    policy_delay: int = 2
    expl_noise: float = 0.1
    huber: bool = True
    block_backend: str = "jnp"         # jnp | fused stack kernel (blocks.py)
    grad_norms: bool = False           # obs taps: grad/update norms per net
    ofenet: Optional[OFENetConfig] = None

    @property
    def z_s_dim(self) -> int:
        return self.ofenet.state_feature_dim if self.ofenet else self.obs_dim

    @property
    def z_sa_dim(self) -> int:
        return (self.ofenet.sa_feature_dim if self.ofenet
                else self.obs_dim + self.act_dim)

    def actor_block(self) -> MLPBlockConfig:
        return MLPBlockConfig(
            in_dim=self.z_s_dim, num_layers=self.num_layers,
            num_units=self.num_units, connectivity=self.connectivity,
            activation=self.activation, out_dim=self.act_dim,
            final_activation="tanh", backend=self.block_backend)

    def critic_block(self) -> MLPBlockConfig:
        return MLPBlockConfig(
            in_dim=self.z_sa_dim, num_layers=self.num_layers,
            num_units=self.num_units, connectivity=self.connectivity,
            activation=self.activation, out_dim=1,
            backend=self.block_backend)


def td3_init(key: PRNGKey, cfg: TD3Config) -> Params:
    ks = split_keys(key, ["actor", "q1", "q2", "ofe"])
    critics = {"q1": mlp_block_init(ks["q1"], cfg.critic_block()),
               "q2": mlp_block_init(ks["q2"], cfg.critic_block())}
    actor = mlp_block_init(ks["actor"], cfg.actor_block())
    params: Params = {
        "actor": actor, "critics": critics,
        "target_actor": jax.tree_util.tree_map(lambda x: x, actor),
        "target_critics": jax.tree_util.tree_map(lambda x: x, critics),
    }
    if cfg.ofenet is not None:
        params["ofenet"] = ofe.ofenet_init(ks["ofe"], cfg.ofenet)
    state = {"params": params,
             "opt": {"actor": adamw_init(actor), "critics": adamw_init(critics)},
             "step": jnp.zeros((), jnp.int32)}
    if cfg.ofenet is not None:
        state["opt"]["ofenet"] = adamw_init(params["ofenet"]["online"])
    return state


def _features(params: Params, cfg: TD3Config, s, a=None):
    if cfg.ofenet is None:
        return s, (None if a is None else jnp.concatenate([s, a], -1))
    z_s, z_sa, _ = ofe.features(params["ofenet"], cfg.ofenet, s, a, train=False)
    return z_s, z_sa


def policy(params: Params, cfg: TD3Config, s: jax.Array,
           which: str = "actor") -> jax.Array:
    z_s, _ = _features(params, cfg, s)
    out, _, _ = mlp_block_apply(params[which], cfg.actor_block(), z_s,
                                train=False)
    return out


def q_values(critics: Params, params: Params, cfg: TD3Config, s, a):
    _, z_sa = _features(params, cfg, s, a)
    q1, feat, _ = mlp_block_apply(critics["q1"], cfg.critic_block(), z_sa,
                                  train=False)
    q2, _, _ = mlp_block_apply(critics["q2"], cfg.critic_block(), z_sa,
                               train=False)
    return q1[..., 0], q2[..., 0], feat


def td3_update(state: Params, cfg: TD3Config, batch: Dict[str, jax.Array],
               key: PRNGKey) -> Tuple[Params, Dict[str, jax.Array]]:
    params = state["params"]
    opt = state["opt"]
    opt_cfg = AdamWConfig(lr=cfg.lr)
    s, a, r = batch["obs"], batch["act"], batch["rew"]
    s2, d = batch["next_obs"], batch["done"]
    # PER importance weights (Schaul et al. 2016 eq. 2); absent key = uniform
    w_is = batch.get("weight")
    metrics: Dict[str, jax.Array] = {}
    new_params = dict(params)
    new_opt = dict(opt)

    if cfg.ofenet is not None:
        ofep, opt_ofe, l_aux, g, upd = ofe.aux_step(
            params["ofenet"], cfg.ofenet, opt["ofenet"], opt_cfg, s, a, s2)
        new_params["ofenet"] = ofep
        new_opt["ofenet"] = opt_ofe
        metrics["aux_loss"] = l_aux
        if cfg.grad_norms:   # obs taps: pure consumers of existing values
            metrics["grad_norm_ofenet"] = tree_l2_norm(g)
            metrics["update_ratio_ofenet"] = tree_update_ratio(
                upd, params["ofenet"]["online"])
    work = new_params

    # --- critic -------------------------------------------------------------
    noise = jnp.clip(cfg.policy_noise * jax.random.normal(key, a.shape),
                     -cfg.noise_clip, cfg.noise_clip)
    a2 = jnp.clip(policy(work, cfg, s2, "target_actor") + noise, -1, 1)
    q1_t, q2_t, _ = q_values(params["target_critics"], work, cfg, s2, a2)
    # n-step batches carry the bootstrap coefficient gamma^span * (1 - done)
    # precomputed as "disc"; 1-step falls back to gamma * (1 - done)
    disc = batch.get("disc")
    if disc is None:
        disc = cfg.gamma * (1.0 - d)
    q_target = jax.lax.stop_gradient(r + disc * jnp.minimum(q1_t, q2_t))

    def critic_loss(critics):
        q1, q2, _ = q_values(critics, work, cfg, s, a)
        e1, e2 = q1 - q_target, q2 - q_target
        l1, l2 = (huber(e1), huber(e2)) if cfg.huber \
            else (0.5 * e1 ** 2, 0.5 * e2 ** 2)
        if w_is is not None:
            return jnp.mean(w_is * l1) + jnp.mean(w_is * l2)
        return jnp.mean(l1) + jnp.mean(l2)

    l_q, g_q = jax.value_and_grad(critic_loss)(params["critics"])
    critics, opt_c = adamw_update(opt_cfg, g_q, opt["critics"],
                                  params["critics"])
    new_params["critics"] = critics
    new_opt["critics"] = opt_c
    if cfg.grad_norms:
        metrics["grad_norm_critics"] = tree_l2_norm(g_q)
        metrics["update_ratio_critics"] = tree_update_ratio(
            critics, params["critics"])

    # --- delayed actor + targets -------------------------------------------
    def actor_loss(actor):
        w = {**work, "actor": actor}
        ai = policy(w, cfg, s)
        q1, _, _ = q_values(critics, w, cfg, s, ai)
        return -jnp.mean(q1)

    do_policy = (state["step"] % cfg.policy_delay) == 0
    l_pi, g_pi = jax.value_and_grad(actor_loss)(params["actor"])
    actor_new, opt_a_new = adamw_update(opt_cfg, g_pi, opt["actor"],
                                        params["actor"])
    # delayed update: select (params, opt state) — zeroing grads would still
    # move params through Adam momentum
    pick = lambda new, old: jax.tree_util.tree_map(
        lambda a, b: jnp.where(do_policy, a, b), new, old)
    actor = pick(actor_new, params["actor"])
    new_params["actor"] = actor
    if cfg.grad_norms:
        # ratio measured on the PICKED params: 0 on delayed (skipped) steps
        metrics["grad_norm_actor"] = tree_l2_norm(g_pi)
        metrics["update_ratio_actor"] = tree_update_ratio(actor,
                                                          params["actor"])
    new_opt["actor"] = pick(opt_a_new, opt["actor"])
    new_params["target_actor"] = ema_update(params["target_actor"], actor,
                                            jnp.where(do_policy, cfg.tau, 0.0))
    new_params["target_critics"] = ema_update(params["target_critics"],
                                              critics, cfg.tau)

    q1, _, feat = q_values(critics, work, cfg, s, a)
    td = jnp.abs(q1 - q_target)
    metrics.update({"critic_loss": l_q, "actor_loss": l_pi,
                    "q_mean": jnp.mean(q1), "td_error": jnp.mean(td)})
    return ({"params": new_params, "opt": new_opt, "step": state["step"] + 1},
            {**metrics, "priorities": td, "q_features": feat})
