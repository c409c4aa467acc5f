"""``bench/flops.py`` against XLA's own count, and the sum-tree bytes
against their shape formula."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import pytest

import flops
import harness

CONFIGS = [c["name"] for c in harness.benchmark()["configs"]]


def _config(name: str, paper: bool, units: int) -> dict:
    """A configuration file, with the paper's method on top when asked
    (MLP-DenseNet trunk with swish, OFENet 4 x 64 DenseNet)."""
    cfg = copy.deepcopy(harness.load_json(
        harness.BENCH / "configs" / f"{name}.json"))
    s = cfg["spec"]
    s["network"]["num_units"] = units
    if paper:
        s["network"].update(connectivity="densenet", activation="swish")
        s["ofenet"].update(enabled=True, num_units=64, num_layers=4,
                           connectivity="densenet")
    return cfg


def _acfg(cfg: dict):
    from repro.rl.envs import make_env
    from repro.rl.experiment import ExperimentSpec
    from repro.rl.policy import algo_config
    spec = ExperimentSpec.from_dict(cfg["spec"])
    return spec, algo_config(spec, make_env(spec.env))


@pytest.mark.parametrize("config", CONFIGS)
def test_update_flops_match_xla(config):
    """At U=256 the model count is within 5 % below XLA's cost analysis of
    the jitted update: XLA adds the elementwise work (activations, Adam,
    the target averages) that the model count leaves out, and removes
    the repeated feature passes the model count never adds."""
    _flops_match_xla(_config(config, False, 256))


@pytest.mark.parametrize("config", CONFIGS)
def test_update_flops_match_xla_with_the_papers_method(config):
    """The same with OFENet and the DenseNet trunk on top, at U=256."""
    _flops_match_xla(_config(config, True, 256))


def _flops_match_xla(cfg: dict):
    from repro.rl import sac
    spec, acfg = _acfg(cfg)
    b = spec.execution.batch_size
    state = jax.eval_shape(lambda k: sac.sac_init(k, acfg), jax.random.key(0))
    shapes = {"obs": (b, 3), "act": (b, 1), "rew": (b,), "next_obs": (b, 3),
              "done": (b,), "weight": (b,)}
    batch = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    compiled = jax.jit(lambda s, bt, k: sac.sac_update(s, acfg, bt, k)).lower(
        state, batch, jax.random.key(0)).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    model = flops.update_flops(cfg)
    assert 1.0 <= ca["flops"] / model <= 1.05, (ca["flops"], model)


def _layer_params(cfg: dict):
    """(the count's parameters per network, the program's own params)."""
    from repro.rl import sac
    _, acfg = _acfg(cfg)
    params = jax.eval_shape(lambda k: sac.sac_init(k, acfg)["params"],
                            jax.random.key(0))
    nets = flops.networks(cfg)
    return nets, {k: sum(i * o + o for i, o in v)
                  for k, v in nets.items()}, params


def test_block_dims_count_parameters():
    """The layer shapes the count works from hold the program's own
    parameters at fig3's U=2048 with OFENet."""
    from repro.common import tree_size
    nets, w, params = _layer_params(_config(CONFIGS[0], True, 2048))
    assert w["actor"] == tree_size(params["actor"])
    assert 2 * w["critic"] == tree_size(params["critics"])
    # fig3's U=2048 trunk: the critic's second layer is (516 + 2048) x 2048
    assert nets["critic"][1] == (516 + 2048, 2048)


@pytest.mark.parametrize("config", CONFIGS)
def test_block_dims_count_the_configs_parameters(config):
    from repro.common import tree_size
    cfg = _config(config, False, harness.load_json(
        harness.BENCH / "configs" / f"{config}.json")["spec"]["network"]
        ["num_units"])
    _, w, params = _layer_params(cfg)
    assert w["actor"] == tree_size(params["actor"]) == cfg["params"]["actor"]
    assert w["critic"] == cfg["params"]["critic_each"]
    assert 2 * w["critic"] == tree_size(params["critics"])


@pytest.mark.parametrize("capacity,depth", [(2, 2), (1000, 11),
                                            (100_000, 18), (131_072, 18),
                                            (1_000_000, 21)])
def test_sumtree_bytes(capacity, depth):
    assert flops.tree_depth(capacity) == depth
    b = 256
    assert flops.sumtree_sample_bytes(capacity, b) == b * 4 * (depth + 3)
    assert flops.sumtree_set_bytes(capacity, b) == b * 4 * (2 * depth + 1)
    cfg = {"spec": {"execution": {"n_core": 2, "n_env": 32,
                                  "distributed": True, "batch_size": b},
                    "replay": {"prioritized": True, "capacity": capacity}}}
    assert flops.sumtree_superstep_bytes(cfg) == (
        b * 4 * (depth + 3) + (b + 64) * 4 * (2 * depth + 1))
    cfg["spec"]["replay"]["prioritized"] = False
    assert flops.sumtree_superstep_bytes(cfg) == 0.0
