"""Soft Actor-Critic with the paper's architecture options.

Policy and twin Q-networks are MLP blocks with selectable connectivity
(mlp / resnet / densenet / d2rl — paper §3.3/§4.2) and width; inputs can be
raw (s, a) or OFENet features (z_s, z_sa) (§3.1). Hyperparameters follow
Haarnoja et al. 2018 (lr 3e-4, tau 5e-3, gamma 0.99, auto entropy tuning);
Huber loss on the critic per paper A.1.

Inside the Trainer's ``repro.update`` scope the update names its parts for
a profiler trace: ``repro.update.critic`` (target, loss, Adam step, target
average and priorities), ``repro.update.actor``, ``repro.update.alpha``;
OFENet's passes carry ``repro.ofenet`` (``core/ofenet.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import (Params, PRNGKey, dense_apply, ema_update, huber,
                          split_keys, tree_l2_norm, tree_size,
                          tree_update_ratio)
from repro.core.blocks import MLPBlockConfig, mlp_block_apply, mlp_block_init
from repro.core.ofenet import OFENetConfig
from repro.core import ofenet as ofe
from repro.optim import AdamWConfig, adamw_init, adamw_update

LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0


@dataclasses.dataclass(frozen=True)
class SACConfig:
    obs_dim: int
    act_dim: int
    num_units: int = 256
    num_layers: int = 2
    connectivity: str = "densenet"     # paper's MLP-DenseNet
    activation: str = "swish"
    gamma: float = 0.99
    tau: float = 0.005
    lr: float = 3e-4
    init_alpha: float = 0.1
    huber: bool = True                 # paper A.1
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
            activation=self.activation, out_dim=2 * self.act_dim,
            backend=self.block_backend)

    def critic_block(self) -> MLPBlockConfig:
        return MLPBlockConfig(
            in_dim=self.z_sa_dim, num_layers=self.num_layers,
            num_units=self.num_units, connectivity=self.connectivity,
            activation=self.activation, out_dim=1,
            backend=self.block_backend)


def sac_init(key: PRNGKey, cfg: SACConfig) -> Params:
    ks = split_keys(key, ["actor", "q1", "q2", "ofe"])
    critics = {"q1": mlp_block_init(ks["q1"], cfg.critic_block()),
               "q2": mlp_block_init(ks["q2"], cfg.critic_block())}
    params: Params = {
        "actor": mlp_block_init(ks["actor"], cfg.actor_block()),
        "critics": critics,
        "target_critics": jax.tree_util.tree_map(lambda x: x, critics),
        "log_alpha": jnp.log(jnp.float32(cfg.init_alpha)),
    }
    if cfg.ofenet is not None:
        params["ofenet"] = ofe.ofenet_init(ks["ofe"], cfg.ofenet)
    state = {
        "params": params,
        "opt": {
            "actor": adamw_init(params["actor"]),
            "critics": adamw_init(params["critics"]),
            "alpha": adamw_init(params["log_alpha"]),
        },
        "step": jnp.zeros((), jnp.int32),
    }
    if cfg.ofenet is not None:
        state["opt"]["ofenet"] = adamw_init(params["ofenet"]["online"])
    return state


def _features(params: Params, cfg: SACConfig, s, a=None, which="online"):
    """(z_s, z_sa) either via OFENet or raw concatenation."""
    if cfg.ofenet is None:
        z_s = s
        z_sa = None if a is None else jnp.concatenate([s, a], -1)
        return z_s, z_sa
    z_s, z_sa, _ = ofe.features(params["ofenet"], cfg.ofenet, s, a,
                                train=False, which=which)
    return z_s, z_sa


def actor_dist(params: Params, cfg: SACConfig, z_s: jax.Array):
    out, _, _ = mlp_block_apply(params["actor"], cfg.actor_block(), z_s,
                                train=False)
    mu, log_std = jnp.split(out, 2, axis=-1)
    log_std = jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX)
    return mu, log_std


def sample_action(params: Params, cfg: SACConfig, s: jax.Array, key: PRNGKey
                  ) -> Tuple[jax.Array, jax.Array]:
    """Tanh-squashed Gaussian sample + log-prob."""
    z_s, _ = _features(params, cfg, s)
    mu, log_std = actor_dist(params, cfg, z_s)
    std = jnp.exp(log_std)
    eps = jax.random.normal(key, mu.shape)
    pre = mu + std * eps
    a = jnp.tanh(pre)
    logp = jnp.sum(-0.5 * (eps ** 2 + 2 * log_std + jnp.log(2 * jnp.pi))
                   - jnp.log(jnp.maximum(1 - a ** 2, 1e-6)), axis=-1)
    return a, logp


def mean_action(params: Params, cfg: SACConfig, s: jax.Array) -> jax.Array:
    z_s, _ = _features(params, cfg, s)
    mu, _ = actor_dist(params, cfg, z_s)
    return jnp.tanh(mu)


def q_values(critics: Params, params: Params, cfg: SACConfig, s, a,
             which="online") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (q1, q2, penultimate feature of q1) — feature for srank."""
    _, z_sa = _features(params, cfg, s, a, which=which)
    q1, feat, _ = mlp_block_apply(critics["q1"], cfg.critic_block(), z_sa,
                                  train=False)
    q2, _, _ = mlp_block_apply(critics["q2"], cfg.critic_block(), z_sa,
                               train=False)
    return q1[..., 0], q2[..., 0], feat


def sac_update(state: Params, cfg: SACConfig, batch: Dict[str, jax.Array],
               key: PRNGKey) -> Tuple[Params, Dict[str, jax.Array]]:
    """One SAC gradient step (+ concurrent OFENet aux step, paper §3.1)."""
    params = state["params"]
    opt = state["opt"]
    opt_cfg = AdamWConfig(lr=cfg.lr)
    s, a, r = batch["obs"], batch["act"], batch["rew"]
    s2, d = batch["next_obs"], batch["done"]
    # PER importance weights (Schaul et al. 2016 eq. 2); absent key = uniform
    w_is = batch.get("weight")
    k1, k2 = jax.random.split(key)
    target_entropy = -float(cfg.act_dim)
    metrics: Dict[str, jax.Array] = {}
    new_params = dict(params)
    new_opt = dict(opt)

    # --- OFENet auxiliary update (decoupled from RL; eq. 1) ---------------
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
    work = new_params   # features below use the refreshed OFENet

    # --- critic update -----------------------------------------------------
    alpha = jnp.exp(params["log_alpha"])
    with jax.named_scope("repro.update.critic"):
        a2, logp2 = sample_action(work, cfg, s2, k1)
        q1_t, q2_t, _ = q_values(params["target_critics"], work, cfg, s2,
                                 a2)
        # bootstrap coefficient: gamma^span * (1 - done). n-step batches
        # carry it precomputed as "disc" (repro.replay.store.nstep_push);
        # 1-step falls back to the usual gamma * (1 - done)
        disc = batch.get("disc")
        if disc is None:
            disc = cfg.gamma * (1.0 - d)
        q_target = r + disc * (jnp.minimum(q1_t, q2_t) - alpha * logp2)
        q_target = jax.lax.stop_gradient(q_target)

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

    # --- actor update ------------------------------------------------------
    def actor_loss(actor):
        w = {**work, "actor": actor}
        ai, logp = sample_action(w, cfg, s, k2)
        q1, q2, _ = q_values(critics, w, cfg, s, ai)
        return jnp.mean(alpha * logp - jnp.minimum(q1, q2)), logp

    with jax.named_scope("repro.update.actor"):
        (l_pi, logp), g_pi = jax.value_and_grad(actor_loss, has_aux=True)(
            params["actor"])
        actor, opt_a = adamw_update(opt_cfg, g_pi, opt["actor"],
                                    params["actor"])
    new_params["actor"] = actor
    new_opt["actor"] = opt_a
    if cfg.grad_norms:
        metrics["grad_norm_actor"] = tree_l2_norm(g_pi)
        metrics["update_ratio_actor"] = tree_update_ratio(actor,
                                                          params["actor"])

    # --- temperature -------------------------------------------------------
    def alpha_loss(log_alpha):
        return -jnp.mean(jnp.exp(log_alpha)
                         * jax.lax.stop_gradient(logp + target_entropy))
    with jax.named_scope("repro.update.alpha"):
        l_al, g_al = jax.value_and_grad(alpha_loss)(params["log_alpha"])
        log_alpha, opt_al = adamw_update(opt_cfg, g_al, opt["alpha"],
                                         params["log_alpha"])
    new_params["log_alpha"] = log_alpha
    new_opt["alpha"] = opt_al

    # --- target nets; priorities for PER: TD error magnitude ---------------
    with jax.named_scope("repro.update.critic"):
        new_params["target_critics"] = ema_update(
            params["target_critics"], critics, cfg.tau)
        q1, q2, feat = q_values(critics, work, cfg, s, a)
        td = jnp.abs(q1 - q_target)
    metrics.update({"critic_loss": l_q, "actor_loss": l_pi,
                    "alpha": jnp.exp(log_alpha), "q_mean": jnp.mean(q1),
                    "td_error": jnp.mean(td)})
    return ({"params": new_params, "opt": new_opt, "step": state["step"] + 1},
            {**metrics, "priorities": td, "q_features": feat})
