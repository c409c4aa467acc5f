"""Vmapped fleet driver tests: member-vs-solo seed parity within the
documented tolerance, early-stop masking that freezes a member bitwise
without perturbing its neighbors, fleet save -> restore -> run resume
parity at a mid-chunk split, fused-vs-chunked dispatch equivalence, grid
partitioning by compiled shape, actionable SpecErrors for unsupported
configs, per-member obs stream demux, and PBT exploit/explore truncation
selection."""
import json

import jax
import numpy as np
import pytest

from repro.rl import Experiment, ExperimentSpec, Fleet, SpecError, Sweep
from repro.rl.sweep import SOLO_PARITY_ATOL, SOLO_PARITY_RTOL

_SMALL = dict(num_units=16, num_layers=1, use_ofenet=False, n_core=1,
              n_env=4, total_steps=12, warmup_steps=8, eval_every=3,
              eval_episodes=1, replay_capacity=256, batch_size=16)


def _small(**overrides):
    return ExperimentSpec().override(**{**_SMALL, **overrides})


def _leaves(tree):
    """Leaves with typed PRNG keys lowered to their raw key data."""
    unkey = jax.tree_util.tree_map(
        lambda x: jax.random.key_data(x)
        if jax.dtypes.issubdtype(getattr(x, "dtype", np.float32),
                                 jax.dtypes.prng_key) else x, tree)
    return [np.asarray(v) for v in jax.tree_util.tree_leaves(unkey)]


def _tree_equal(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b)))


def _member_state(fleet, m):
    return jax.device_get(
        jax.tree_util.tree_map(lambda v: v[m], fleet._fls))


# ---------------------------------------------------------- solo parity

def test_member_matches_solo_run_within_tolerance():
    spec = _small()
    fleet = Fleet([spec.override(seed=s) for s in (0, 1, 2)])
    fleet.run(12)
    solo = Experiment.from_spec(spec.override(seed=1))
    res = solo.run(12)
    fr = fleet.results()[1]
    assert fr.eval_steps == res.eval_steps
    np.testing.assert_allclose(fr.returns, res.returns,
                               rtol=SOLO_PARITY_RTOL, atol=SOLO_PARITY_ATOL)
    for a, b in zip(_leaves(_member_state(fleet, 1).agent["params"]),
                    _leaves(solo._ls.agent["params"])):
        np.testing.assert_allclose(a, b, rtol=SOLO_PARITY_RTOL,
                                   atol=SOLO_PARITY_ATOL)


# ----------------------------------------------------- early-stop masking

def test_freeze_is_bitwise_and_does_not_perturb_neighbors():
    spec = _small()
    fleet = Fleet([spec.override(seed=s) for s in (0, 1, 2)])
    twin = Fleet([spec.override(seed=s) for s in (0, 1, 2)])
    fleet.run(6)
    twin.run(6)
    frozen = _member_state(fleet, 1)
    fleet.set_done([1])
    fleet.run(6)
    twin.run(6)
    # the frozen member's whole carry (params, replay, actors, key) is
    # untouched; its history stops accruing
    assert _tree_equal(_member_state(fleet, 1), frozen)
    assert fleet.eval_steps[1] == [3, 6]
    # neighbors advanced bitwise exactly as in the never-frozen twin fleet
    for m in (0, 2):
        assert _tree_equal(_member_state(fleet, m), _member_state(twin, m))
        assert fleet.returns[m] == twin.returns[m]
    # unfreezing resumes from the frozen carry
    fleet.set_done([1], False)
    fleet.run(3)
    assert fleet.eval_steps[1] == [3, 6, 15]


# ------------------------------------------------------------ resume parity

def test_fleet_save_restore_resume_parity_mid_chunk(tmp_path):
    spec = _small()
    path = str(tmp_path / "fleet.npz")
    full = Fleet([spec.override(seed=s) for s in (0, 1)])
    full.run(12)

    part = Fleet([spec.override(seed=s) for s in (0, 1)])
    part.run(5)                    # mid eval-period split (eval_every=3)
    part.save(path)
    back = Fleet.restore(path)
    assert back.step == 5
    back.run(7)
    assert _tree_equal(back._fls, full._fls)
    assert back.returns == full.returns
    assert back.eval_steps == full.eval_steps


def test_fused_and_chunked_dispatch_agree_bitwise():
    spec = _small()
    fused = Fleet([spec.override(seed=s) for s in (0, 1)])
    fused.run(12)                               # one fused device program
    chunked = Fleet([spec.override(seed=s) for s in (0, 1)])
    chunked.run(12, stop_at_return=float("inf"))  # per-segment dispatch
    assert not any(chunked.done)
    assert _tree_equal(fused._fls, chunked._fls)
    assert fused.returns == chunked.returns


# ------------------------------------------------------------- validation

def test_pallas_kernel_fleet_is_rejected():
    with pytest.raises(SpecError, match="kernel"):
        Fleet([_small(replay_kernel="pallas")])


def test_shape_heterogeneous_members_are_rejected_with_paths():
    with pytest.raises(SpecError, match="num_units"):
        Fleet([_small(num_units=16), _small(num_units=32)])


def test_from_grid_partitions_by_compiled_shape():
    sweep = Sweep.from_grid(_small(), axis={"num_units": [16, 24]}, seeds=2)
    assert len(sweep.fleets) == 2          # one sub-fleet per width
    assert [len(p) for p in sweep.partition] == [2, 2]
    assert "num_units=16" in sweep.describe()
    res = sweep.run(6)
    assert len(res) == 4
    # results come back in grid order, not partition order
    assert [r.point["num_units"] for r in res] == [16, 16, 24, 24]
    assert [r.seed for r in res] == [0, 1, 0, 1]
    assert all(len(r.result.returns) == 2 for r in res)


# --------------------------------------------------------------- obs demux

def test_obs_streams_demux_per_member(tmp_path):
    spec = _small(**{"obs.log_dir": str(tmp_path / "sweep"),
                     "obs.enabled": True, "obs.sinks": "jsonl"})
    fleet = Fleet([spec.override(seed=s) for s in (0, 1)],
                  labels=["seed=0", "seed=1"])
    fleet.run(6)
    fleet.close()
    dirs = sorted(p.name for p in (tmp_path / "sweep").iterdir())
    assert dirs == ["seed=0", "seed=1"]
    rows = {}
    for d in dirs:
        lines = [json.loads(l) for l in
                 (tmp_path / "sweep" / d / "metrics.jsonl")
                 .read_text().splitlines()]
        assert lines, d
        assert all(r.get("member") == d for r in lines if "member" in r)
        rows[d] = [r for r in lines if r.get("kind") == "eval"]
    # distinct member streams: different seeds -> different eval returns
    r0 = [r["return"] for r in rows["seed=0"]]
    r1 = [r["return"] for r in rows["seed=1"]]
    assert r0 and r1 and r0 != r1


# ------------------------------------------------------------------- PBT

def test_exploit_explore_truncation_selection():
    spec = _small()
    fleet = Fleet([spec.override(seed=s) for s in range(4)])
    fleet.run(6)
    before = [_member_state(fleet, m) for m in range(4)]
    report = fleet.exploit_explore(fraction=0.25,
                                   scores=[3.0, 0.0, 2.0, 1.0])
    # exactly one loser (member 1) copies the winner's (member 0) agent
    assert report["copied"] == {fleet.labels[1]: fleet.labels[0]}
    after1 = _member_state(fleet, 1)
    assert _tree_equal(after1.agent, before[0].agent)
    # the loser keeps its own replay/actors/key; others are untouched
    assert _tree_equal(after1.replay, before[1].replay)
    assert _tree_equal(after1.key, before[1].key)
    for m in (0, 2, 3):
        assert _tree_equal(_member_state(fleet, m), before[m])
    # fleet keeps running after the copy
    fleet.run(3)
    assert all(len(r) == 3 for r in fleet.returns)


def test_exploit_explore_noise_perturbs_only_losers():
    spec = _small()
    fleet = Fleet([spec.override(seed=s) for s in range(4)])
    fleet.run(6)
    before = [_member_state(fleet, m) for m in range(4)]
    fleet.exploit_explore(fraction=0.25, noise_scale=0.1,
                          scores=[3.0, 0.0, 2.0, 1.0])
    after1 = _member_state(fleet, 1)
    # perturbed copy: close to the winner's params but not identical
    winner = _leaves(before[0].agent["params"])
    got = _leaves(after1.agent["params"])
    assert not all(np.array_equal(a, b) for a, b in zip(got, winner))
    for a, b in zip(got, winner):
        np.testing.assert_allclose(a, b, rtol=0.5, atol=0.5)
    for m in (0, 2, 3):
        assert _tree_equal(
            _member_state(fleet, m).agent, before[m].agent)
