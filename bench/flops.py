"""Operations and bytes the algorithm needs, counted from shapes.

Model FLOPs count the matrix multiplications a SAC superstep requires
(2 flops per multiply-add), once each: a forward pass that the update
computes twice on the same inputs and weights (the OFENet state features
of one batch, read by three losses) counts once. Elementwise work
(activations, Adam, the target averages) is not counted.

Per update, with batch B and n actors:

* OFENet auxiliary step: forward of phi_s, phi_sa and the predictor on B,
  backward with weight and input gradients (no input gradient into the
  observations);
* critic target: phi_s and the actor on the next observations, phi_sa and
  both target critics on the sampled next actions;
* critic loss: phi_s and phi_sa on (s, a), both critics forward and
  backward (weight gradients, input gradients above the first layer);
* actor loss: the actor forward and backward, phi_sa and both updated
  critics forward with input gradients only (the path to the action);
* priorities: the updated first critic forward;
* collect: phi_s and the actor on the n actors' observations.

Sum-tree bytes are those of proportional descent over a binary tree of
partial sums of depth ``ceil(log2(capacity)) + 1``: a sample reads one
node per level and writes a leaf index and priority; a set writes a leaf
and rewrites every ancestor.
"""
from __future__ import annotations

import math
from typing import List, Tuple

Dims = List[Tuple[int, int]]


def block_dims(in_dim: int, layers: int, units: int, connectivity: str,
               out_dim: int = 0) -> Dims:
    """(in, out) of each dense layer of a hidden stack (+ output layer)."""
    dims, d = [], in_dim
    for _ in range(layers):
        dims.append((d, units))
        d = d + units if connectivity == "densenet" else units
    if out_dim:
        dims.append((d, out_dim))
    return dims


def feature_dim(in_dim: int, layers: int, units: int, connectivity: str) -> int:
    if connectivity == "densenet":
        return in_dim + layers * units
    return units if layers else in_dim


def fwd(dims: Dims, rows: int) -> float:
    return sum(2.0 * rows * i * o for i, o in dims)


def bwd(dims: Dims, rows: int, weights: bool = True,
        first_input: bool = True) -> float:
    """Backward flops: weight gradients and/or input gradients."""
    f = 0.0
    for k, (i, o) in enumerate(dims):
        if weights:
            f += 2.0 * rows * i * o
        if first_input or k > 0:
            f += 2.0 * rows * i * o
    return f


def networks(cfg: dict) -> dict:
    """Dense-layer dims of every network of a configuration file."""
    s, e = cfg["spec"], cfg["env_dims"]
    n, o = s["network"], s["ofenet"]
    obs, act = e["obs"], e["act"]
    nets = {}
    if o["enabled"]:
        zs = feature_dim(obs, o["num_layers"], o["num_units"], o["connectivity"])
        zsa = feature_dim(zs + act, o["num_layers"], o["num_units"],
                          o["connectivity"])
        nets["phi_s"] = block_dims(obs, o["num_layers"], o["num_units"],
                                   o["connectivity"])
        nets["phi_sa"] = block_dims(zs + act, o["num_layers"], o["num_units"],
                                    o["connectivity"])
        nets["pred"] = [(zsa, obs)]
    else:
        zs, zsa = obs, obs + act
    u, L, c = n["num_units"], n["num_layers"], n["connectivity"]
    nets["actor"] = block_dims(zs, L, u, c, 2 * act)
    nets["critic"] = block_dims(zsa, L, u, c, 1)
    return nets


def update_flops(cfg: dict) -> float:
    """Model FLOPs of one SAC (+ OFENet) update on one batch."""
    b = cfg["spec"]["execution"]["batch_size"]
    nets = networks(cfg)
    ofe = "phi_s" in nets
    f = 0.0
    if ofe:
        aux = nets["phi_s"] + nets["phi_sa"] + nets["pred"]
        f += fwd(aux, b) + bwd(aux, b, first_input=False)
        f += 2 * fwd(nets["phi_s"], b)                      # s2 and s
        f += 3 * fwd(nets["phi_sa"], b)                     # (s2,a2) (s,a) (s,ai)
        f += bwd(nets["phi_sa"], b, weights=False)          # d/d ai
    f += fwd(nets["actor"], b)                              # a2 on s2
    f += 2 * fwd(nets["critic"], b)                         # target critics
    f += 2 * (fwd(nets["critic"], b)
              + bwd(nets["critic"], b, first_input=False))  # critic loss
    f += fwd(nets["actor"], b) + bwd(nets["actor"], b, first_input=False)
    f += 2 * (fwd(nets["critic"], b) + bwd(nets["critic"], b, weights=False))
    f += fwd(nets["critic"], b)                             # priorities
    return f


def collect_flops(cfg: dict) -> float:
    x = cfg["spec"]["execution"]
    n = x["n_core"] * x["n_env"] if x["distributed"] else 1
    nets = networks(cfg)
    return fwd(nets.get("phi_s", []) + nets["actor"], n)


def superstep_flops(cfg: dict) -> float:
    """Model FLOPs per update of the training superstep."""
    return update_flops(cfg) + collect_flops(cfg)


def tree_depth(capacity: int) -> int:
    return int(math.ceil(math.log2(max(capacity, 2)))) + 1


def sumtree_sample_bytes(capacity: int, batch: int) -> float:
    """Read a target, one node per level below the root and the leaf;
    write the leaf index and priority (4-byte words)."""
    return batch * 4.0 * (tree_depth(capacity) + 3)


def sumtree_set_bytes(capacity: int, batch: int) -> float:
    """Read index and value, write the leaf, read and write each of the
    ``depth - 1`` ancestors."""
    d = tree_depth(capacity)
    return batch * 4.0 * (2 + 1 + 2 * (d - 1))


def sumtree_superstep_bytes(cfg: dict, shards: int = 1) -> float:
    """Sum-tree bytes per update: one batch sample, the batch's priority
    refresh and the priorities of the rows the actors add."""
    s = cfg["spec"]
    x, r = s["execution"], s["replay"]
    if not r["prioritized"]:
        return 0.0
    n = x["n_core"] * x["n_env"] if x["distributed"] else 1
    cap, b = r["capacity"] // shards, x["batch_size"]
    return (sumtree_sample_bytes(cap, b) + sumtree_set_bytes(cap, b)
            + sumtree_set_bytes(cap, n))
