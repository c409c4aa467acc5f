"""Layered experiment API tests: spec-tree validation (invalid combos and
the removed python-loop / host-replay paths fail at construction and on
restore), to_dict/from_dict serialization incl. unknown-key forward compat,
override semantics, checkpoint-metadata round-trip through
checkpoint/ckpt.py, the preset registry building every paper scenario
without jit, and save/restore resume parity: interrupted == uninterrupted
BITWISE (returns, final params, replay state) at ANY split point —
chunk-boundary and mid-period — for both sum-tree kernels x n-step 1 and 3,
plus a 4-fake-device mesh smoke at a non-boundary split."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.rl import (Experiment, ExperimentSpec, SpecError, SpecWarning,
                      parse_overrides, presets)
from repro.rl.runner import Trainer

_SMALL = dict(num_units=16, num_layers=1, use_ofenet=False,
              distributed=True, n_core=1, n_env=4, total_steps=12,
              warmup_steps=8, eval_every=3, eval_episodes=1,
              replay_capacity=256, batch_size=16)


def _small(**overrides):
    return ExperimentSpec().override(**{**_SMALL, **overrides})


# --------------------------------------------------------------- validation

def test_field_choice_errors_are_actionable():
    with pytest.raises(SpecError, match="connectivity"):
        _small(connectivity="dense_net")
    with pytest.raises(SpecError, match="activation"):
        _small(activation="mish")
    with pytest.raises(SpecError, match="spec.env"):
        _small(env="ant")
    with pytest.raises(SpecError, match="loop"):
        _small(loop="while")
    with pytest.raises(SpecError, match="batch_size"):
        _small(batch_size=0)


_REMOVED_PATHS = [("loop", "python", "execution.loop"),
                  ("replay_backend", "host", "replay.backend")]


@pytest.mark.parametrize("key,value,field", _REMOVED_PATHS)
def test_removed_paths_are_rejected(key, value, field):
    """The per-step python loop and the host replay are gone: asking for
    either names the field and what replaced it."""
    with pytest.raises(SpecError, match=field) as e:
        _small(**{key: value})
    assert "scan driver over the device replay" in str(e.value)


@pytest.mark.parametrize("key,value,field", _REMOVED_PATHS)
def test_restore_rejects_a_removed_path_in_checkpoint_metadata(
        key, value, field, tmp_path):
    """A checkpoint whose spec asks for a removed path fails on restore
    with the same SpecError, not deep inside the run."""
    d = _small().to_dict()
    section, name = field.split(".")
    d[section][name] = value
    path = str(tmp_path / "old.npz")
    ckpt.save(path, {"x": jnp.zeros(2)}, metadata={"spec": d})
    with pytest.raises(SpecError, match=field):
        Experiment.restore(path)


def test_mesh_requires_device_backend_and_divisibility():
    with pytest.raises(SpecError, match="divide"):
        _small(mesh_shards=3)                   # 3 ∤ n_actors=4
    assert _small(mesh_shards=2).execution.mesh_shards == 2


def test_fused_blocks_reject_ofenet_batch_norm():
    with pytest.raises(SpecError, match="fused"):
        _small(use_ofenet=True, block_backend="fused",
               **{"ofenet.batch_norm": True})
    # BN off is the supported paper setting
    _small(use_ofenet=True, block_backend="fused")


def test_trainer_consumes_the_spec_natively():
    """No flat config view sits between the spec and the Trainer."""
    assert not hasattr(ExperimentSpec(), "to_run_config")
    assert Trainer(presets.get("smoke")).spec is presets.get("smoke")


# ------------------------------------------------------------- serialization

def test_dict_round_trip_and_override():
    spec = _small(algo="td3", n_step=3, **{"network.connectivity": "d2rl"})
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec
    # dotted path and flat alias hit the same field
    assert (spec.override(**{"network.num_units": 64})
            == spec.override(num_units=64))
    assert spec.override(num_units=64).network.num_units == 64
    # overrides never mutate
    assert spec.network.num_units == 16


def test_override_unknown_key_raises():
    with pytest.raises(SpecError, match="unknown override"):
        ExperimentSpec().override(num_unitz=64)
    with pytest.raises(SpecError, match="unknown override"):
        ExperimentSpec().override(**{"network.width": 64})
    with pytest.raises(SpecError, match="unknown override"):
        ExperimentSpec().override(**{"network": 64})  # section, not field


def test_from_dict_skips_unknown_keys_forward_compat():
    spec = _small()
    d = spec.to_dict()
    d["future_section"] = {"x": 1}
    d["network"] = dict(d["network"], future_knob=7)
    d["version"] = 99
    with pytest.warns(SpecWarning, match="unknown"):
        assert ExperimentSpec.from_dict(d) == spec


def test_ckpt_metadata_round_trip():
    """spec -> ckpt.save(metadata=...) -> load_metadata -> from_dict parity
    (the Experiment.save/restore self-description contract)."""
    import tempfile, os
    spec = _small(algo="td3", replay_kernel="pallas", n_step=3)
    path = os.path.join(tempfile.mkdtemp(), "ck.npz")
    ckpt.save(path, {"x": jnp.arange(3.0)}, metadata=spec.to_dict())
    meta = ckpt.load_metadata(path)
    assert ExperimentSpec.from_dict(meta) == spec


def test_parse_overrides_literals_and_strings():
    ov = parse_overrides(["num_units=64", "replay.backend=device",
                          "use_ofenet=False", "tau=0.5",
                          "distributed=false", "prioritized=TRUE"])
    assert ov == {"num_units": 64, "replay.backend": "device",
                  "use_ofenet": False, "tau": 0.5,
                  "distributed": False, "prioritized": True}
    with pytest.raises(SpecError, match="key=value"):
        parse_overrides(["oops"])


def test_bool_fields_reject_truthy_strings():
    """A shell-style 'false' that slipped past parsing must fail loudly,
    never run the wrong ablation as a truthy string."""
    for key in ("use_ofenet", "distributed", "prioritized",
                "ofenet.batch_norm"):
        with pytest.raises(SpecError, match="bool"):
            _small(**{key: "false"})


# ------------------------------------------------------------ preset registry

def test_every_preset_constructs_validates_and_builds():
    """Tier-1 bitrot guard: the full registry builds Experiments with no
    jit execution (mirrored by benchmarks.run --smoke)."""
    assert {"fig1-depth", "fig3-width", "fig5-connectivity", "fig6-ofenet",
            "fig8-distributed", "table1-ours",
            "table1-orig"} <= set(presets.names())
    for name in presets.names():
        spec = presets.get(name)                      # constructs+validates
        assert ExperimentSpec.from_dict(spec.to_dict()) == spec
        exp = Experiment.from_spec(spec)              # builds the Trainer
        assert exp.step == 0 and exp._ls is None      # nothing executed


def test_preset_register_rejects_duplicates_and_junk():
    with pytest.raises(SpecError, match="unknown preset"):
        presets.get("fig99-nope")
    with pytest.raises(SpecError, match="already registered"):
        presets.register("smoke", presets.get("smoke"))
    with pytest.raises(SpecError, match="ExperimentSpec"):
        presets.register("junk-preset", object())


# ------------------------------------------------------------- resume parity

def _final_params(exp):
    return jax.tree_util.tree_leaves(exp._ls.agent["params"])


def _assert_replay_state_equal(a, b):
    """Bitwise replay-state equality over the ReplayState pytree."""
    for x, y in zip(jax.tree_util.tree_leaves(a._ls.replay),
                    jax.tree_util.tree_leaves(b._ls.replay)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_bitwise_resume(spec, split, total, tmp_path):
    """run(split); save; restore; run(total-split) must bitwise-match an
    uninterrupted run(total): eval returns, final params, replay state."""
    full = Experiment.from_spec(spec)
    r_full = full.run(total)

    part = Experiment.from_spec(spec)
    part.run(split)
    path = str(tmp_path / "ck.npz")
    part.save(path)

    res = Experiment.restore(path)
    assert res.spec == spec                      # spec from ckpt metadata
    assert res.step == split
    r_res = res.run(total - split)

    assert r_res.returns == r_full.returns
    assert r_res.eval_steps == r_full.eval_steps
    for a, b in zip(_final_params(full), _final_params(res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _assert_replay_state_equal(full, res)
    return r_res


_KERNEL_NSTEP = [("xla", 1), ("xla", 3), ("pallas", 1), ("pallas", 3)]


@pytest.mark.parametrize("kernel,n_step", _KERNEL_NSTEP)
def test_save_restore_resume_parity(kernel, n_step, tmp_path):
    """run(6); save; restore; run(6) bitwise-matches an uninterrupted
    run(12) — the chunk-boundary split, for both sum-tree kernels, with
    and without n-step returns."""
    spec = _small(replay_kernel=kernel, n_step=n_step)
    r = _assert_bitwise_resume(spec, split=6, total=12, tmp_path=tmp_path)
    assert r.eval_steps == [3, 6, 9, 12]


@pytest.mark.parametrize("kernel,n_step", _KERNEL_NSTEP)
def test_resume_parity_mid_period_split(kernel, n_step, tmp_path):
    """The resume-ANYWHERE guarantee: a split in the middle of an eval
    period is bitwise too. This re-chunks the step sequence (12 = 3+2 |
    1+3+3 vs 3+3+3+3), which is only bitwise because the chunk is ONE
    lax.scan with carried outputs — the superstep compiles identically for
    every chunk length (no trailing unrolled superstep)."""
    spec = _small(replay_kernel=kernel, n_step=n_step)
    _assert_bitwise_resume(spec, split=5, total=12, tmp_path=tmp_path)


def test_resume_parity_mid_period_split_nstep(tmp_path):
    """n-step returns ride the checkpoint bitwise at a mid-period split
    (the rollback ring is part of the saved TrainLoopState)."""
    spec = _small(n_step=3)
    _assert_bitwise_resume(spec, split=7, total=12, tmp_path=tmp_path)


_MESH_RESUME = r"""
import os, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
warnings.simplefilter("ignore")
import numpy as np, jax
from repro.rl import Experiment, ExperimentSpec

ckpt_path = os.environ["MESH_RESUME_CKPT"]
spec = ExperimentSpec().override(
    num_units=16, num_layers=1, use_ofenet=False, distributed=True,
    n_core=1, n_env=8, total_steps=10, warmup_steps=16, eval_every=5,
    eval_episodes=1, replay_capacity=512, batch_size=16, mesh_shards=4)
full = Experiment.from_spec(spec)
r_full = full.run(10)
part = Experiment.from_spec(spec)
part.run(3)                                   # non-boundary split
part.save(ckpt_path)
res = Experiment.restore(ckpt_path)
r_res = res.run(7)
assert r_res.returns == r_full.returns, (r_res.returns, r_full.returns)
for a, b in zip(jax.tree_util.tree_leaves(full._ls.agent["params"]),
                jax.tree_util.tree_leaves(res._ls.agent["params"])):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree_util.tree_leaves(full._ls.replay),
                jax.tree_util.tree_leaves(res._ls.replay)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK")
"""


def test_resume_parity_mesh_mid_period_split(tmp_path):
    """4-fake-device mesh smoke: the sharded scan superstep inherits the
    bitwise resume-anywhere guarantee (subprocess, like test_train_loop)."""
    import os, subprocess, sys
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["MESH_RESUME_CKPT"] = str(tmp_path / "mesh_resume.npz")
    r = subprocess.run([sys.executable, "-c", _MESH_RESUME],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_restore_preserves_eval_history_and_metrics_rows(tmp_path):
    spec = _small()
    exp = Experiment.from_spec(spec)
    exp.run(6)
    path = str(tmp_path / "ck.npz")
    exp.save(path)
    res = Experiment.restore(path)
    assert res.returns == exp.returns and res.eval_steps == exp.eval_steps
    # dispatch accounting continues across the resume
    assert res.trainer.dispatches == exp.trainer.dispatches
    rows = list(res.metrics())
    assert [r["step"] for r in rows] == [3, 6]
    assert all("return" in r and "critic_loss" in r for r in rows)
    res.run(6)
    assert [r["step"] for r in res.metrics()] == [3, 6, 9, 12]


def test_restore_without_metadata_fails_loudly(tmp_path):
    path = str(tmp_path / "bare.npz")
    ckpt.save(path, {"x": jnp.zeros(2)})
    with pytest.raises(FileNotFoundError, match="Experiment.save"):
        Experiment.restore(path)


# -------------------------------------------------------------- run determinism

def test_experiment_run_is_deterministic():
    """Two fresh handles on the same spec produce identical results,
    including keep_last payloads (the PR-2/PR-3 parity tests lean on this)."""
    spec = _small()
    r_a = Experiment.from_spec(spec).run(12, eval_at_end=True,
                                         keep_last=True)
    r_b = Experiment.from_spec(spec).run(12, eval_at_end=True,
                                         keep_last=True)
    assert r_a.returns == r_b.returns
    assert r_a.eval_steps == r_b.eval_steps
    np.testing.assert_array_equal(r_a.last_priorities, r_b.last_priorities)
    assert r_a.state is not None and r_b.state is not None
