"""Compile the RL path's Pallas kernels, and the benchmark's training
chunk, for a TPU v5e that is described, not attached.

``get_topology_desc`` describes the chip and the installed TPU compiler
lowers and compiles for it, so what Mosaic refuses (an op it cannot lower,
a kernel past the scoped-VMEM limit) fails here at no chip time, and the
compiled program's layouts and temporary memory can be read. Nothing runs,
so these tests say nothing about results or speed.

The topology is described only inside the module fixture: one process at a
time may load the TPU library, and every xdist worker imports this file.
Keep these tests in this one file, so one worker loads it. The persistent
compile cache is off around them: entries compiled for a described chip
cannot be read back without one.
"""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.core.blocks import MLPBlockConfig
from repro.kernels.dense_block import stack
from repro.kernels.replay_tree import ref as rt_ref
from repro.kernels.replay_tree.replay_tree import tree_sample, tree_set_onehot
from repro.rl.experiment import Experiment, ExperimentSpec

CAPACITY, BATCH = 100_000, 256     # the paper budget's replay and batch

# the widest (L, U) per connectivity that stack.py claims for the chip
STACK_CLAIMS = [("densenet", 2, 1024), ("densenet", 4, 512),
                ("densenet", 8, 128), ("d2rl", 2, 2048), ("d2rl", 8, 1024),
                ("mlp", 2, 1024), ("mlp", 4, 512), ("mlp", 8, 256)]

# the benchmark's training configuration: SAC 2x256, one actor, pendulum
# (act_dim 1), 1e6-row device replay through the Pallas sum-tree
TRAIN_CONFIG = (Path(__file__).resolve().parents[1]
                / "bench" / "configs" / "sac-mlp-u256.json")
PAPER_CONFIG = TRAIN_CONFIG.with_name("sac-densenet-u2048.json")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile_mosaic(fn, *args) -> None:
    """Compile ``fn`` for the chip; the program must hold a Mosaic kernel."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo


@pytest.mark.parametrize("capacity", [CAPACITY, 1_000_000])
def test_tree_sample_compiles_for_v5e(one_chip, capacity):
    """The sample stages no whole tree: neither the program's temporaries
    nor any of its kernels' scoped VMEM reach a 1e6-row tree's 8 MiB (the
    whole-tree one-hot descent used 10.75 MiB of VMEM at 1e6 rows)."""
    size = rt_ref.tree_size(capacity)
    limit = 4 * rt_ref.tree_size(1_000_000)
    compiled = jax.jit(
        lambda t, x: tree_sample(t, x, capacity=capacity, interpret=False)
    ).lower(jax.ShapeDtypeStruct((size,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((BATCH,), jnp.float32, sharding=one_chip)
            ).compile()
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < limit
    kernels = [line for line in hlo.splitlines()
               if re.match(r"\s*%tree_sample\.\d+ = .* custom-call\(", line)]
    assert kernels
    for line in kernels:
        used = re.search(r'"used_scoped_memory_configs":\[[^]]*"size":"(\d+)"',
                         line)
        assert used and int(used.group(1)) < limit, line[:200]


def test_tree_set_onehot_compiles_for_v5e(one_chip):
    size = rt_ref.tree_size(CAPACITY)
    _compile_mosaic(
        lambda t, i, v: tree_set_onehot(t, i, v, interpret=False),
        jax.ShapeDtypeStruct((size,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((BATCH,), jnp.float32, sharding=one_chip))


def _paper_agent(monkeypatch, **override):
    """The Trainer of the paper's agent (2x2048 DenseNet SAC with OFENet,
    64 actors, 111/8 widths, 1e6-row replay through the Pallas sum-tree),
    with Mosaic kernels compiled for the described chip."""
    monkeypatch.setattr(repro.kernels, "mosaic_available", lambda: True)
    spec = ExperimentSpec.from_dict(json.loads(PAPER_CONFIG.read_text())
                                    ["spec"]).override(**override)
    return Experiment.from_spec(spec).trainer


def _on_chip(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("capacity", [CAPACITY, 1_000_000])
def test_tree_kernels_ask_for_room_for_a_large_tree(one_chip, monkeypatch,
                                                    capacity):
    """In the paper agent's training chunk XLA does not place the tree in
    VMEM before the sum-tree kernels, so each stages its own copy: at 1e6
    rows (8 MiB) the add's 64-row set asks for 22.97 MiB and does not
    compile at Mosaic's default 16 MiB limit. The kernels ask for the room
    a large tree needs; a small tree keeps the default."""
    trainer = _paper_agent(monkeypatch, replay_capacity=capacity)
    state = _on_chip(jax.eval_shape(lambda: trainer._fresh_state()[0]),
                     one_chip)
    hlo = jax.jit(trainer.chunk_fn(2, False).__wrapped__).lower(state) \
        .compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo


def test_paper_agent_warmup_compiles_for_v5e(one_chip, monkeypatch):
    """The warm-up adds its 9 984 rows in one replay add; the one-hot
    sum-tree set takes them in pieces (its (n, n) masks do not compile at
    that many leaves)."""
    trainer = _paper_agent(monkeypatch)
    warm = trainer.warmup_steps // trainer.n_actors

    def warmup(seed, key):
        ls, _ = trainer._fresh_state(seed)
        return trainer._op_collect_add(
            trainer._rand_policy, ls.agent["params"], ls.actors, ls.nstep,
            ls.replay, key, ls.step, steps=warm, drop=0)
    _compile_mosaic(
        warmup, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip))


@pytest.mark.parametrize("connectivity,layers,units", STACK_CLAIMS)
def test_dense_stack_fwd_bwd_compiles_for_v5e(one_chip, connectivity, layers,
                                              units):
    d0 = 256
    cfg = MLPBlockConfig(in_dim=d0, num_layers=layers, num_units=units,
                         connectivity=connectivity)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def loss(x, ws, bs):
        return jnp.sum(stack.dense_stack(
            x, ws, bs, connectivity=connectivity, impl="pallas",
            interpret=False) ** 2)

    _compile_mosaic(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    shape(BATCH, d0),
                    tuple(shape(d, units) for d in cfg.layer_in_dims()),
                    tuple(shape(units) for _ in range(layers)))


def test_train_chunk_keeps_replay_columns_compact_for_v5e(one_chip,
                                                          monkeypatch):
    """The superstep's replay add must not make XLA:TPU carry a store
    column lane-padded: a scatter into the f32[capacity, 1] ``act`` column
    got the (8, 128) tiling, 512 MB of temporaries for 4 MB of data, and a
    relayout of the whole column on every update. A short chunk compiles to
    the same layouts as the benchmark's 500-update one."""
    # compile Mosaic kernels (not interpret mode) for the described chip
    monkeypatch.setattr(repro.kernels, "mosaic_available", lambda: True)
    spec = ExperimentSpec.from_dict(json.loads(TRAIN_CONFIG.read_text())
                                    ["spec"])
    trainer = Experiment.from_spec(spec).trainer
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: trainer._fresh_state()[0]))
    compiled = jax.jit(trainer.chunk_fn(4, False).__wrapped__) \
        .lower(state).compile()
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    cap = spec.replay.capacity
    padded = re.findall(rf"f32\[{cap},1\]\{{1,0:T\(8,128\)", hlo)
    assert not padded, f"{len(padded)} lane-padded replay columns"
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
