"""OFENet — Online Feature Extractor Network (paper §3.1, faithful).

Learns state features  z_s = phi_s(s)  and state-action features
z_sa = phi_sa(z_s, a), each an N-layer MLP-DenseNet (Swish, optional BN),
trained *decoupled from RL* with the auxiliary loss

    L_aux = E[ || f_pred(z_sa_target) - s_{t+1} ||^2 ]            (eq. 1)

where f_pred is a single linear layer. Per paper A.1, a *target* OFENet
(Polyak EMA, tau=0.005) stabilizes training under the Ape-X-style replay;
the RL agent consumes features from the *online* network.

Dimensionality intentionally grows: with densenet connectivity the emitted
feature is dim(s) + L*U (e.g. 111 -> 2159 on Ant with L=8, U=256), matching
Table 2 of the paper.

Batch norm (``batch_norm``): the auxiliary step normalizes by the batch's
statistics and refreshes the running ones (momentum 0.99, ``_bn_apply``);
every other read of the features (acting, the critic targets and losses,
the actor loss) normalizes by the running statistics. Every pass runs
under the ``repro.ofenet`` name scope, so a profiler trace can put device
time on it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import Params, PRNGKey, dense_apply, dense_init, ema_update, split_keys
from repro.core.blocks import MLPBlockConfig, mlp_block_apply, mlp_block_init
from repro.optim import AdamWConfig, adamw_update

SCOPE = "repro.ofenet"


@dataclasses.dataclass(frozen=True)
class OFENetConfig:
    state_dim: int
    action_dim: int
    num_layers: int = 8          # paper A.4: 8-layer DenseNet
    num_units: int = 256         # per-layer growth; scaled up in the width study
    connectivity: str = "densenet"
    activation: str = "swish"
    batch_norm: bool = True      # paper uses BN inside OFENet
    tau: float = 0.005           # target-net smoothing (paper A.1)
    block_backend: str = "jnp"   # jnp | fused (BN-off only; see blocks.py)

    @property
    def state_block(self) -> MLPBlockConfig:
        return MLPBlockConfig(
            in_dim=self.state_dim, num_layers=self.num_layers,
            num_units=self.num_units, connectivity=self.connectivity,
            activation=self.activation, batch_norm=self.batch_norm,
            backend=self.block_backend)

    @property
    def sa_block(self) -> MLPBlockConfig:
        return MLPBlockConfig(
            in_dim=self.state_feature_dim + self.action_dim,
            num_layers=self.num_layers, num_units=self.num_units,
            connectivity=self.connectivity, activation=self.activation,
            batch_norm=self.batch_norm, backend=self.block_backend)

    @property
    def state_feature_dim(self) -> int:
        return self.state_block.feature_dim

    @property
    def sa_feature_dim(self) -> int:
        return self.sa_block.feature_dim


def ofenet_init(key: PRNGKey, cfg: OFENetConfig) -> Params:
    ks = split_keys(key, ["phi_s", "phi_sa", "pred"])
    online = {
        "phi_s": mlp_block_init(ks["phi_s"], cfg.state_block),
        "phi_sa": mlp_block_init(ks["phi_sa"], cfg.sa_block),
        # f_pred: linear map z_sa -> s_{t+1}   (eq. 1)
        "pred": dense_init(ks["pred"], cfg.sa_feature_dim, cfg.state_dim),
    }
    return {"online": online, "target": jax.tree_util.tree_map(lambda x: x, online)}


def features(params: Params, cfg: OFENetConfig, s: jax.Array,
             a: Optional[jax.Array] = None, *, train: bool = False,
             which: str = "online", axis_name: Optional[str] = None
             ) -> Tuple[jax.Array, Optional[jax.Array], Params]:
    """Compute (z_s, z_sa, refreshed-params). ``z_sa`` is None when ``a`` is None."""
    net = params[which]
    with jax.named_scope(SCOPE):
        z_s, _, new_phi_s = mlp_block_apply(
            net["phi_s"], cfg.state_block, s, train=train,
            axis_name=axis_name)
        z_sa, new_phi_sa = None, net["phi_sa"]
        if a is not None:
            z_sa, _, new_phi_sa = mlp_block_apply(
                net["phi_sa"], cfg.sa_block,
                jnp.concatenate([z_s, a], axis=-1), train=train,
                axis_name=axis_name)
    new_net = {**net, "phi_s": new_phi_s, "phi_sa": new_phi_sa}
    return z_s, z_sa, {**params, which: new_net}


def aux_loss(params: Params, cfg: OFENetConfig, s: jax.Array, a: jax.Array,
             s_next: jax.Array, *, axis_name: Optional[str] = None
             ) -> Tuple[jax.Array, Params]:
    """Auxiliary next-state prediction loss (eq. 1), on the online network."""
    _, z_sa, new_params = features(params, cfg, s, a, train=True,
                                   which="online", axis_name=axis_name)
    pred = dense_apply(params["online"]["pred"], z_sa)
    loss = jnp.mean(jnp.sum(jnp.square(pred - s_next), axis=-1))
    return loss, new_params


def target_update(params: Params, cfg: OFENetConfig) -> Params:
    return {**params, "target": ema_update(params["target"], params["online"], cfg.tau)}


def _with_stats(net: Params, fresh: Params) -> Params:
    """``net`` with the batch-norm running statistics of ``fresh``."""
    def block(b, f):
        return {**b, "layers": [
            {**l, "bn": {**l["bn"], "mean": fl["bn"]["mean"],
                         "var": fl["bn"]["var"]}} if "bn" in l else l
            for l, fl in zip(b["layers"], f["layers"])]}
    return {**net, "phi_s": block(net["phi_s"], fresh["phi_s"]),
            "phi_sa": block(net["phi_sa"], fresh["phi_sa"])}


def aux_step(params: Params, cfg: OFENetConfig, opt_state, opt_cfg:
             AdamWConfig, s: jax.Array, a: jax.Array, s_next: jax.Array):
    """One Adam step of the online network on the auxiliary loss (eq. 1),
    decoupled from RL, then the target's Polyak step. The batch-norm
    running statistics (zero gradient: the loss reads batch statistics)
    take the values the step's forward pass refreshed.

    Returns ``(params, opt_state, loss, grads, online_after_adam)``."""
    def loss_fn(online):
        return aux_loss({**params, "online": online}, cfg, s, a, s_next)

    with jax.named_scope(SCOPE):
        (loss, fresh), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params["online"])
        upd, opt_state = adamw_update(opt_cfg, g, opt_state,
                                      params["online"])
        if cfg.batch_norm:
            upd = _with_stats(upd, fresh["online"])
        new = target_update({**params, "online": upd}, cfg)
    return new, opt_state, loss, g, upd
