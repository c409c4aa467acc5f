"""Observability tests: sinks round-trip rows in order (incl. the async
BufferedWriter, whose errors surface at drain), the stream layer filters on
ABSOLUTE steps so both loop drivers emit the identical row set, enabling the
obs stream changes training outputs bitwise NOT AT ALL, resume stays bitwise
with a JSONL sink attached (both drivers x both replay backends), and the
run-report CLI summarizes a real run directory and flags instabilities."""
import json
import math
import threading
import time

import jax
import numpy as np
import pytest

from repro.obs.report import SPIKE_FACTOR, load_rows, summarize
from repro.obs.stream import ObsRun
from repro.obs.trace import TraceCapture, annotate
from repro.obs.writers import (BufferedWriter, CsvWriter, JsonlWriter,
                               MemoryWriter)
from repro.rl import Experiment, ExperimentSpec, ObsSpec, SpecError

_SMALL = dict(num_units=16, num_layers=1, use_ofenet=False,
              distributed=True, n_core=1, n_env=4, total_steps=12,
              warmup_steps=8, eval_every=3, eval_episodes=1,
              replay_capacity=256, batch_size=16)


def _small(**overrides):
    return ExperimentSpec().override(**{**_SMALL, **overrides})


def _obs(log_dir, sinks=("jsonl", "memory"), log_every=1, **kw):
    return {"obs.enabled": True, "obs.sinks": sinks,
            "obs.log_dir": str(log_dir), "obs.log_every": log_every, **kw}


# ------------------------------------------------------------------- writers

def test_jsonl_writer_round_trips_rows(tmp_path):
    w = JsonlWriter(str(tmp_path / "metrics.jsonl"))
    rows = [{"kind": "train", "step": 1, "critic_loss": 0.5},
            {"kind": "eval", "step": 2, "return": -100.0},
            {"kind": "event", "event": "chunk", "step": 2, "steps": 2}]
    w.write(rows[:2])
    w.write(rows[2:])
    w.close()
    assert load_rows(str(tmp_path)) == rows


def test_jsonl_appends_and_report_dedups_last_wins(tmp_path):
    """Resume replays steps into the same file; readers keep the LAST row
    per (kind, step, event)."""
    a = JsonlWriter(str(tmp_path / "metrics.jsonl"))
    a.write([{"kind": "train", "step": 5, "loss": 1.0}])
    a.close()
    b = JsonlWriter(str(tmp_path / "metrics.jsonl"))   # append, not truncate
    b.write([{"kind": "train", "step": 5, "loss": 2.0},
             {"kind": "train", "step": 10, "loss": 3.0}])
    b.close()
    rows = load_rows(str(tmp_path))
    assert [(r["step"], r["loss"]) for r in rows] == [(5, 2.0), (10, 3.0)]


def test_csv_writer_pins_header_to_first_row(tmp_path):
    w = CsvWriter(str(tmp_path / "metrics.csv"))
    w.write([{"kind": "train", "step": 1, "a": 1.0}])
    w.write([{"kind": "train", "step": 2, "b": 9.0},      # unknown col drops
             {"kind": "train", "step": 3, "a": 3.0}])
    w.close()
    lines = (tmp_path / "metrics.csv").read_text().splitlines()
    assert lines[0] == "kind,step,a"
    assert lines[1:] == ["train,1,1.0", "train,2,", "train,3,3.0"]


def test_buffered_writer_preserves_order_across_batches():
    mem = MemoryWriter()
    bw = BufferedWriter([mem], maxsize=4)        # small queue: forces blocking
    for i in range(100):
        bw.write([{"kind": "train", "step": i, "i": i}])
    bw.drain()
    assert [r["step"] for r in mem.rows] == list(range(100))
    bw.close()


def test_buffered_writer_fans_out_and_survives_concurrent_drain():
    m1, m2 = MemoryWriter(), MemoryWriter()
    bw = BufferedWriter([m1, m2])
    stop = threading.Event()

    def pound():
        i = 0
        while not stop.is_set():
            bw.write([{"kind": "train", "step": i}])
            i += 1
    t = threading.Thread(target=pound)
    t.start()
    time.sleep(0.05)
    stop.set()
    t.join()
    bw.drain()
    assert m1.rows == m2.rows and len(m1.rows) > 0
    bw.close()


class _BoomWriter:
    # a sink BUG (non-OSError): retried zero times, surfaced at drain.
    # transient/permanent OSError retry semantics live in test_guard.py
    def __init__(self):
        self.calls = 0

    def write(self, rows):
        self.calls += 1
        if self.calls == 1:
            raise ValueError("boom: sink bug")

    def flush(self):
        pass

    def close(self):
        pass


def test_buffered_writer_errors_surface_at_drain_not_in_thread():
    bw = BufferedWriter([_BoomWriter()])
    bw.write([{"kind": "train", "step": 1}])
    with pytest.raises(ValueError, match="sink bug"):
        bw.drain()
    bw.write([{"kind": "train", "step": 2}])     # writer still usable
    bw.drain()                                   # error was consumed
    bw.close()
    with pytest.raises(RuntimeError, match="closed"):
        bw.write([{"kind": "train", "step": 3}])


# ------------------------------------------------------------------ ObsSpec

def test_obsspec_validation():
    with pytest.raises(SpecError, match="log_dir"):
        ObsSpec(enabled=True, sinks=("jsonl",))          # file sink, no dir
    with pytest.raises(SpecError, match="log_dir"):
        ObsSpec(enabled=True, sinks=("memory",), trace=2)  # trace needs dir
    with pytest.raises(SpecError, match="sinks"):
        ObsSpec(sinks=("tensorboard",))
    with pytest.raises(SpecError, match="log_every"):
        ObsSpec(log_every=0)
    # CLI convenience: a comma-separated string normalizes to a tuple
    assert ObsSpec(sinks="memory").sinks == ("memory",)
    assert ObsSpec(sinks="jsonl,csv", log_dir="d").sinks == ("jsonl", "csv")
    # round-trips through the spec tree
    spec = _small(**_obs("runs/x", log_every=7))
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec
    assert spec.obs.log_every == 7 and spec.obs.enabled


def test_stream_downsamples_on_absolute_steps():
    """Re-chunking the same step sequence never moves a row: the filter is
    ``step % log_every == 0`` against absolute steps, whatever the chunk
    boundaries — the property that makes obs resume/eval-stop invariant."""
    def run_chunks(bounds):
        obs = ObsRun(ObsSpec(enabled=True, log_every=5, sinks=("memory",)))
        start = 0
        for stop in bounds:
            n = stop - start
            obs.flush_chunk(start, {"loss": np.arange(n) + start + 1.0})
            start = stop
        obs.drain()                  # rows reach the sink asynchronously
        return [(r["step"], r["loss"]) for r in obs.rows]

    expect = [(5, 5.0), (10, 10.0), (15, 15.0)]
    assert run_chunks([15]) == expect
    assert run_chunks([7, 15]) == expect                 # mid-period split
    assert run_chunks([3, 6, 9, 12, 15]) == expect
    # the python driver's per-step path produces the identical row set
    obs = ObsRun(ObsSpec(enabled=True, log_every=5, sinks=("memory",)))
    for s in range(1, 16):
        obs.log_train(s, {"loss": float(s)})
    obs.drain()
    assert [(r["step"], r["loss"]) for r in obs.rows] == expect


def test_obsrun_disabled_is_inert():
    obs = ObsRun(ObsSpec())
    obs.flush_chunk(0, {"loss": np.ones(8)})
    obs.log_train(1, {"loss": 1.0})
    obs.log_eval(1, -10.0, {})
    obs.log_event("chunk", step=1, steps=1)
    obs.drain()
    assert obs.rows == [] and obs.rows_written == 0
    assert obs.trace.status == "idle"
    obs.close()


def test_trace_capture_lifecycle(tmp_path):
    tc = TraceCapture(2, str(tmp_path / "trace"))
    assert tc.status == "pending"
    tc.begin()
    if tc.status.startswith("failed"):           # no profiler backend here
        pytest.skip(f"profiler unavailable: {tc.status}")
    assert tc.status == "active"
    tc.begin()                                   # idempotent while active
    tc.end()
    assert tc.status == "active" and tc.remaining == 1
    tc.end()
    assert tc.status == "done" and not tc.active
    tc.finish()                                  # no-op after done
    assert (tmp_path / "trace").is_dir()
    with annotate("repro.test"):                 # host annotation: no-op ok
        pass


def test_trace_capture_starts_without_the_python_tracer(tmp_path,
                                                       monkeypatch):
    seen = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options=None: seen.append(
                            profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tc = TraceCapture(1, str(tmp_path / "trace"))
    tc.begin()
    tc.end()
    assert tc.status == "done"
    (opts,) = seen
    assert opts.python_tracer_level == 0


def test_scan_driver_spans_its_blocking_host_steps(tmp_path, monkeypatch):
    """The scan driver's host steps between dispatches each run inside a
    named host span, so an ``obs.trace`` capture shows what the host did
    while the device waited."""
    import repro.rl.experiment as experiment
    names = []
    real = experiment.annotate

    def record(name):
        names.append(name)
        return real(name)
    monkeypatch.setattr(experiment, "annotate", record)
    spec = _small(loop="scan", replay_backend="device", **_obs(
        tmp_path, sinks=("memory",)), **{"guard.enabled": True})
    Experiment.from_spec(spec).run(6)
    # two chunks, each ending on an eval point (eval_every=3)
    assert names == 2 * ["repro.chunk_dispatch", "repro.obs.flush",
                         "repro.guard.check", "repro.obs.flush",
                         "repro.eval_readback"]


# ------------------------------------------------------- bitwise on/off

@pytest.mark.parametrize("backend,loop", [("host", "python"),
                                          ("host", "scan"),
                                          ("device", "python"),
                                          ("device", "scan")])
def test_obs_stream_is_bitwise_invisible(backend, loop, tmp_path):
    """Enabling the default stream (grad-norm taps on, per-step cadence,
    jsonl+memory sinks) changes NOTHING trained: eval returns, final params
    and last sampled priorities are bitwise-identical to the obs-off run."""
    base = dict(_SMALL, replay_backend=backend, loop=loop)
    r_off = Experiment.from_spec(ExperimentSpec().override(**base)) \
        .run(eval_at_end=True, keep_last=True)
    exp = Experiment.from_spec(ExperimentSpec().override(
        **base, **_obs(tmp_path / f"{backend}_{loop}")))
    r_on = exp.run(eval_at_end=True, keep_last=True)
    assert r_on.returns == r_off.returns
    assert r_on.eval_steps == r_off.eval_steps
    np.testing.assert_array_equal(r_on.last_priorities, r_off.last_priorities)
    for a, b in zip(jax.tree_util.tree_leaves(r_off.state["params"]),
                    jax.tree_util.tree_leaves(r_on.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the stream actually recorded the run (every step at log_every=1)
    train = [r for r in exp.obs.rows if r["kind"] == "train"]
    assert [r["step"] for r in train] == list(range(1, 13))
    assert all("grad_norm_critics" in r and "update_ratio_critics" in r
               for r in train)
    exp.close()


# ------------------------------------------------------- resume parity

@pytest.mark.parametrize("backend,loop", [("host", "python"),
                                          ("host", "scan"),
                                          ("device", "python"),
                                          ("device", "scan")])
def test_resume_parity_with_jsonl_sink(backend, loop, tmp_path):
    """Bitwise resume at a mid-period split with the full obs stack attached
    (jsonl+memory sinks, per-step cadence): sink io never perturbs the PR-5
    contract, and the appended metrics.jsonl still reads back as one
    consistent run (dedup last-wins over the replayed steps)."""
    spec = _small(replay_backend=backend, loop=loop,
                  **_obs(tmp_path / "run"))
    full = Experiment.from_spec(spec)
    r_full = full.run(12)

    part = Experiment.from_spec(spec)
    part.run(5)
    path = str(tmp_path / "ck.npz")
    part.save(path)
    res = Experiment.restore(path)
    assert res.spec == spec                       # obs spec rides the ckpt
    assert res.obs.rows_written == part.obs.rows_written
    r_res = res.run(7)

    assert r_res.returns == r_full.returns
    assert r_res.eval_steps == r_full.eval_steps
    for a, b in zip(jax.tree_util.tree_leaves(full._ls.agent["params"]),
                    jax.tree_util.tree_leaves(res._ls.agent["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full.close(), part.close(), res.close()

    rows = load_rows(str(tmp_path / "run"))
    train = [r for r in rows if r["kind"] == "train"]
    assert [r["step"] for r in train] == list(range(1, 13))
    marks = [r["event"] for r in rows if r["kind"] == "event"
             and r["event"] in ("save", "restore")]
    assert marks == ["save", "restore"]


# ------------------------------------------------------------ report CLI

def test_report_on_real_run_dir(tmp_path, capsys):
    """End-to-end: scan run with jsonl sink -> load_rows/summarize -> the
    summary carries throughput, grad norms and eval; the CLI renders it."""
    spec = _small(loop="scan", replay_backend="device", srank_every=6,
                  **_obs(tmp_path, log_every=2))
    exp = Experiment.from_spec(spec)
    exp.run(12, eval_at_end=True)
    exp.close()

    s = summarize(load_rows(str(tmp_path)))
    assert s["counts"]["train"] == 6 and s["counts"]["eval"] >= 4
    assert s["steps"] == {"first": 2, "last": 12}
    assert s["throughput"]["steps"] == 12
    assert s["throughput"]["steps_per_sec"] > 0
    assert s["throughput"]["chunks"] == 4                 # eval_every=3
    assert set(s["grad_norms"]) == {"grad_norm_actor", "grad_norm_critics"}
    assert s["grad_norms"]["grad_norm_actor"]["n"] == 6
    assert {"update_ratio_actor",
            "update_ratio_critics"} <= set(s["update_ratios"])
    assert "critic_loss" in s["losses"] and "td_error" in s["losses"]
    assert s["staleness"]                                 # device backend
    assert s["srank"] is not None and s["srank"]["n"] == 2
    assert s["eval"]["n"] >= 4 and s["eval"]["best_return"] is not None

    from repro.obs import report
    assert report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "throughput:" in out and "grad_norm_critics" in out
    assert report.main([str(tmp_path), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["counts"]["train"] == 6


def test_report_flags_spikes_nonfinite_and_srank_collapse(tmp_path):
    w = JsonlWriter(str(tmp_path / "metrics.jsonl"))
    base = [{"kind": "train", "step": s, "critic_loss": 1.0,
             "grad_norm_actor": 2.0} for s in (1, 2, 3, 4, 5)]
    base[3]["critic_loss"] = SPIKE_FACTOR * 1.0 + 1.0     # spike at step 4
    base[4]["grad_norm_actor"] = math.inf                 # non-finite
    w.write(base)
    w.write([{"kind": "event", "event": "srank", "step": 2, "srank": 40.0},
             {"kind": "event", "event": "srank", "step": 5, "srank": 10.0}])
    w.close()
    s = summarize(load_rows(str(tmp_path)))
    why = {(f["metric"], f["step"]): f["why"] for f in s["instability"]}
    assert "spike" in why[("critic_loss", 4)]
    assert why[("grad_norm_actor", 5)] == "non-finite"
    assert "collapse" in why[("srank", 5)]


def test_load_rows_rejects_bad_schema(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text('{"kind": "train"}\n')                   # missing step
    with pytest.raises(ValueError, match="kind/step"):
        load_rows(str(tmp_path))
    p.write_text("not json\n")
    with pytest.raises(ValueError, match="JSONL"):
        load_rows(str(tmp_path))
    with pytest.raises(FileNotFoundError, match="jsonl sink"):
        load_rows(str(tmp_path / "nope"))
