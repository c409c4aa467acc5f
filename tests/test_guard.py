"""Guard subsystem tests: durable-store atomicity (staged commits, torn
saves, checksum fallback, retention), health-guard detection at the exact
step with halt/skip/rollback recovery bitwise-reconstructible from
``fold_in`` ordinals, per-member fleet rollback that leaves neighbors
undisturbed, BufferedWriter transient-IO retry, and the crash-safe
supervisor whose SIGKILL auto-resume matches an uninterrupted run
bit-for-bit. Every fault is injected via ``repro.guard.chaos`` —
deterministic, step-addressed — so each recovery claim is exercised, not
trusted."""
import json
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.guard import chaos
from repro.guard.monitor import GuardViolation
from repro.guard.store import CheckpointCorrupt, DurableStore
from repro.obs.writers import BufferedWriter, MemoryWriter
from repro.rl import Experiment, ExperimentSpec, Fleet, SpecError

_SMALL = dict(num_units=16, num_layers=1, use_ofenet=False, n_core=1,
              n_env=4, total_steps=12, warmup_steps=8, eval_every=3,
              eval_episodes=1, replay_capacity=256, batch_size=16)


def _small(**overrides):
    return ExperimentSpec().override(**{**_SMALL, **overrides})


def _guarded(policy="halt", **overrides):
    return _small(**{"guard.enabled": True, "guard.policy": policy,
                     **overrides})


def _leaves(tree):
    unkey = jax.tree_util.tree_map(
        lambda x: jax.random.key_data(x)
        if jax.dtypes.issubdtype(getattr(x, "dtype", np.float32),
                                 jax.dtypes.prng_key) else x, tree)
    return [np.asarray(v) for v in jax.tree_util.tree_leaves(unkey)]


def _tree_equal(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b)))


def _npz_saver(value):
    def save(path):
        np.savez(path, x=np.full(8, value, dtype=np.float32))
    return save


# ------------------------------------------------------------ DurableStore

def test_store_commit_verify_restore(tmp_path):
    st = DurableStore(str(tmp_path), keep=5)
    for s in (10, 20, 30):
        st.save(_npz_saver(s), s)
    assert [DurableStore.step_of(p) for p in st.checkpoints()] == [10, 20, 30]
    assert st.latest_step() == 30
    for p in st.checkpoints():
        st.verify(p)                                   # all healthy
    best = st.restore_latest()
    assert DurableStore.step_of(best) == 30
    x = np.load(DurableStore.payload(best))["x"]
    assert np.all(x == 30)


def test_store_retention_keeps_last_k(tmp_path):
    st = DurableStore(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        st.save(_npz_saver(s), s)
    assert [DurableStore.step_of(p) for p in st.checkpoints()] == [3, 4]


def test_store_aborted_save_leaves_previous_good(tmp_path):
    st = DurableStore(str(tmp_path), keep=3)
    st.save(_npz_saver(1), 10)
    st._pre_commit_hook = lambda staging: (_ for _ in ()).throw(
        RuntimeError("chaos: die before commit"))
    with pytest.raises(RuntimeError, match="die before commit"):
        st.save(_npz_saver(2), 20)
    st._pre_commit_hook = None
    # the aborted step-20 save must not exist in any form
    assert [DurableStore.step_of(p) for p in st.checkpoints()] == [10]
    assert DurableStore.step_of(st.restore_latest()) == 10


def test_store_stale_staging_is_invisible_and_cleanable(tmp_path):
    st = DurableStore(str(tmp_path), keep=3)
    st.save(_npz_saver(1), 10)
    # a SIGKILLed save leaves a staging dir behind: never listed, never
    # restorable, removed by startup hygiene
    torn = tmp_path / "staging-99999-deadbeef"
    torn.mkdir()
    (torn / "state.npz").write_bytes(b"partial garbage")
    assert len(st.checkpoints()) == 1
    assert st.clean_staging() == 1
    assert not torn.exists()
    assert DurableStore.step_of(st.restore_latest()) == 10


def test_store_corrupt_fallback_and_exhaustion(tmp_path):
    st = DurableStore(str(tmp_path), keep=5)
    for s in (10, 20, 30):
        st.save(_npz_saver(s), s)
    chaos.corrupt_checkpoint(st.checkpoints()[-1], mode="bitflip")
    bad = []
    best = st.restore_latest(on_bad=bad.append)
    assert DurableStore.step_of(best) == 20
    assert len(bad) == 1 and isinstance(bad[0], CheckpointCorrupt)
    assert "checksum" in bad[0].reason
    chaos.corrupt_checkpoint(st.checkpoints()[0], mode="truncate")
    chaos.corrupt_checkpoint(st.checkpoints()[1], mode="truncate")
    bad2 = []
    assert st.restore_latest(on_bad=bad2.append) is None
    assert len(bad2) == 3
    assert "truncated" in bad2[-1].reason or "size" in bad2[-1].reason


# -------------------------------------------------------------- spec wiring

def test_guard_spec_validation():
    with pytest.raises(SpecError, match="policy"):
        _guarded(policy="restart")
    with pytest.raises(SpecError, match="srank"):
        # srank guard needs the eval srank probe actually running
        _guarded(**{"guard.srank_collapse": 10, "eval.srank_every": 0})
    with pytest.raises(SpecError, match="spike_factor"):
        _guarded(**{"guard.spike_factor": -1.0})


def test_fleet_rejects_skip_policy():
    with pytest.raises(SpecError, match="skip"):
        Fleet([_guarded("skip", seed=s) for s in (0, 1)])


# ------------------------------------------------------- detection + halt

def test_guarded_run_is_bitwise_invisible():
    plain = Experiment.from_spec(_small())
    plain.run(12)
    guarded = Experiment.from_spec(_guarded("halt"))
    guarded.run(12)
    assert _tree_equal(plain._ls, guarded._ls)
    assert plain.returns == guarded.returns


def test_halt_reports_exact_detection_step():
    exp = Experiment.from_spec(_guarded("halt"))
    chaos.arm_nan_step(exp.trainer, at_step=10)
    with pytest.raises(GuardViolation) as gv:
        exp.run(12)
    viols = gv.value.violations
    assert any(v.reason == "nonfinite_stream" for v in viols)
    # the counter reads at_step once that update retires, so the poisoned
    # superstep is the NEXT one: detection is exact, at step 11
    assert min(v.step for v in viols) == 11
    assert gv.value.recoveries == 0


def test_persistent_fault_exhausts_recovery_budget(tmp_path):
    # a traced fault re-fires on every replay: skip must spend its whole
    # budget and then raise with the history attached
    exp = Experiment.from_spec(
        _guarded("skip", **{"guard.max_recoveries": 2}))
    chaos.arm_nan_step(exp.trainer, at_step=10)
    with pytest.raises(GuardViolation) as gv:
        exp.run(12)
    assert gv.value.recoveries == 2


# ------------------------------------------------- rollback determinism

def test_rollback_recovery_is_reconstructible(tmp_path):
    exp = Experiment.from_spec(_guarded("rollback"))
    store = DurableStore(str(tmp_path), keep=3)
    exp.attach_guard(store)
    exp.run(6)
    store.save(lambda p: exp.save(p), 6)
    payload = DurableStore.payload(store.checkpoints()[-1])
    chaos.poison_params(exp)                  # transient host fault
    exp.run(6)                                # detect -> rollback -> finish
    assert exp.step == 12
    assert all(np.isfinite(v).all()
               for v in _leaves(exp._ls.agent["params"]))
    # documented contract: recovery == restore + fold_in(ordinal) + rerun
    ref = Experiment.restore(payload)
    ref._ls = ref._ls._replace(key=jax.random.fold_in(ref._ls.key, 1))
    ref.run(6)
    assert _tree_equal(exp._ls, ref._ls)


def test_rollback_without_store_raises():
    exp = Experiment.from_spec(_guarded("rollback"))
    exp.run(6)
    chaos.poison_params(exp)
    with pytest.raises(GuardViolation, match="store"):
        exp.run(6)


def test_fleet_member_rollback_leaves_neighbors_bitwise(tmp_path):
    def build():
        return Fleet([_guarded("rollback", seed=s) for s in (0, 1)])

    control = build()
    control.run(12)

    fleet = build()
    store = DurableStore(str(tmp_path), keep=3)
    fleet.attach_guard(store)
    fleet.run(6)
    store.save(lambda p: fleet.save(p), 6)
    chaos.poison_params(fleet, member=1)
    fleet.run(6)                              # member 1 rolls back to 6
    assert fleet.step == 12
    # healthy member 0: bitwise identical to the fault-free control fleet
    m0 = jax.tree_util.tree_map(lambda v: v[0], fleet._fls)
    c0 = jax.tree_util.tree_map(lambda v: v[0], control._fls)
    assert _tree_equal(m0, c0)
    # recovered member 1: finite, and == restored ckpt + fold_in ordinal
    p1 = _leaves(jax.tree_util.tree_map(lambda v: v[1],
                                        fleet._fls.agent["params"]))
    assert all(np.isfinite(v).all() for v in p1)
    # lockstep contract: the member does NOT replay the lost interval — it
    # restarts from the step-6 checkpoint with the fold_in-perturbed key
    # and runs only the fleet's REMAINING schedule (the one segment after
    # the detecting one, 9->12)
    good = Fleet.restore(DurableStore.payload(store.checkpoints()[0]))
    good._fls = good._fls._replace(key=jax.vmap(
        lambda k: jax.random.fold_in(k, 1))(good._fls.key))
    good.run(3)
    m1 = jax.tree_util.tree_map(lambda v: v[1], fleet._fls)
    g1 = jax.tree_util.tree_map(lambda v: v[1], good._fls)
    assert _tree_equal(m1, g1)


# ------------------------------------------------------- BufferedWriter IO

def test_buffered_writer_retries_transient_oserror():
    healthy = MemoryWriter()
    flaky = chaos.FlakySink(MemoryWriter(), fails=2)
    bw = BufferedWriter([flaky, healthy], retries=3, backoff=0.001)
    bw.write([{"kind": "train", "step": 1}])
    bw.drain()                                 # no raise: retried through
    assert flaky.attempts == 3 and flaky.delivered == 1
    assert len(healthy.rows) == 1              # healthy sink: no duplicates
    bw.close()


def test_buffered_writer_surfaces_permanent_oserror_at_drain():
    flaky = chaos.FlakySink(MemoryWriter(), fails=None)
    bw = BufferedWriter([flaky], retries=2, backoff=0.001)
    bw.write([{"kind": "train", "step": 1}])
    with pytest.raises(OSError, match="transient sink IO error"):
        bw.drain()
    assert flaky.attempts == 3                 # 1 try + 2 retries


# ------------------------------------------------------------- supervisor

def test_supervisor_sigkill_resume_is_bitwise(tmp_path, monkeypatch):
    from repro.guard import supervise
    # worker subprocesses import repro: point them at this checkout
    src = str(Path(__file__).resolve().parent.parent / "src")
    monkeypatch.setenv(
        "PYTHONPATH",
        src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    # the worker is a CLI entry point and would cache compiles; tests don't
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "false")

    killed = tmp_path / "killed"
    rc = supervise.main([
        "smoke", "--dir", str(killed), "--steps", "12", "--save-every", "6",
        "--retries", "2", "--backoff", "0.01", "--chaos", "kill-in-save@6"])
    assert rc == 0
    res = json.loads((killed / "result.json").read_text())
    inc = json.loads((killed / "incident.json").read_text())
    assert res["step"] == 12
    assert inc["status"] == "ok"
    assert inc["attempts"][0]["signal"] == "SIGKILL"
    assert inc["attempts"][-1]["exit_code"] == 0
    assert not list((killed / "ckpts").glob("staging-*"))

    # uninterrupted in-process reference: identical params, identical evals
    from repro.rl import presets
    ref = Experiment.from_spec(presets.get("smoke"))
    ref.run(12)
    assert res["params_sha256"] == supervise._digest(
        ref._ls.agent["params"])
    assert res["returns"] == [float(r) for r in ref.returns]


def test_supervisor_budget_spent_writes_incident(tmp_path, monkeypatch):
    from repro.guard import supervise
    src = str(Path(__file__).resolve().parent.parent / "src")
    monkeypatch.setenv(
        "PYTHONPATH",
        src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    # the worker is a CLI entry point and would cache compiles; tests don't
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "false")

    run = tmp_path / "halted"
    rc = supervise.main([
        "smoke", "--dir", str(run), "--steps", "12", "--save-every", "6",
        "--retries", "0", "--backoff", "0.01", "--chaos", "nan@6",
        "--override", "guard.enabled=true",
        "--override", "guard.policy=halt"])
    assert rc == supervise.EXIT_BUDGET_SPENT
    inc = json.loads((run / "incident.json").read_text())
    assert inc["status"] == "failed"
    att = inc["attempts"][0]
    assert att["exit_code"] == supervise.EXIT_GUARD
    assert any(v["reason"] == "nonfinite_params" for v in att["violations"])


_PARENT_ONLY = r"""
import sys
import jax._src.xla_bridge as xb
from repro.guard import supervise

spawned = []


class _Done:
    returncode = 0


def _fake_run(argv, *a, **k):
    spawned.append(argv)
    return _Done()


supervise.subprocess.run = _fake_run
rc = supervise.main(["smoke", "--dir", sys.argv[1], "--retries", "0"])
assert rc == 0 and spawned and spawned[0][-1] == "--worker", spawned
assert not xb.backends_are_initialized(), "supervisor parent touched JAX"
print("OK")
"""


def test_supervisor_parent_never_initializes_a_backend(tmp_path):
    """The parent only respawns workers. On an accelerator host a parent
    that initialized a backend would hold the chip its worker needs, so
    it must never do so (fresh interpreter, worker spawn stubbed out)."""
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _PARENT_ONLY, str(tmp_path)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
