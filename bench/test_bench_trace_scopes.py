"""``bench/trace_scopes.py``: the phase table, the in-program/between idle
split and the host/device clock bracket, on a synthetic trace built from
a text proto, on the small v5e trace ``test_bench_trace.py`` reads, and on
``bench/data/scoped_trace.xplane.pb.gz``: the scanned training chunk of
``orig-u256-train``'s configuration (ten updates per chunk, two chunks
inside a ``bench.traced_window`` annotation) recorded on a v5e chip by
``bench/record_scoped_trace.py``."""
from __future__ import annotations

import numpy as np
import pytest

import harness
import trace_reduce
import trace_scopes

SMALL = harness.BENCH / "data" / "small_trace.xplane.pb"
SCOPED = harness.BENCH / "data" / "scoped_trace.xplane.pb.gz"
WINDOW = "bench.traced_window"
PHASES = ("repro.collect", "repro.replay.add", "repro.replay.sample",
          "repro.update", "repro.replay.refresh")

# Device clock = host clock - 1000 ns. Two programs, [100, 900] and
# [1200, 1800] on the device clock, each launched by the host (a launch
# encloses an execute whose flow reaches an enqueue that produces the
# program's flow) and completed by a callback. Launch starts 900 and 2100,
# callbacks 2000 and 2900 (host): lo = max(800, 900), hi = min(1100, 1100).
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 20 offset_ps: 100000 duration_ps: 800000
      stats { metadata_id: 1 int64_value: 1 }
      stats { metadata_id: 2 int64_value: -11 } }
    events { metadata_id: 20 offset_ps: 1200000 duration_ps: 600000
      stats { metadata_id: 1 int64_value: 2 }
      stats { metadata_id: 2 int64_value: -22 } }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 100000 duration_ps: 800000 }
    events { metadata_id: 11 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 12 offset_ps: 400000 duration_ps: 200000 }
    events { metadata_id: 13 offset_ps: 700000 duration_ps: 200000 }
    events { metadata_id: 14 offset_ps: 1200000 duration_ps: 300000 }
    events { metadata_id: 15 offset_ps: 1600000 duration_ps: 200000 }
  }
  event_metadata { key: 20 value { id: 20 name: "jit_chunk(1)" } }
  event_metadata { key: 10 value { id: 10 name: "%while.1 = () while()" } }
  event_metadata { key: 11 value { id: 11 name: "%fusion.1 = f32[] fusion()"
    stats { metadata_id: 3
      str_value: "jit(chunk)/while/body/repro.collect/dot_general:" } } }
  event_metadata { key: 12 value { id: 12 name: "%scatter.2 = f32[] scatter()"
    stats { metadata_id: 3 ref_value: 4 } } }
  event_metadata { key: 13 value { id: 13 name: "%fusion.3 = f32[] fusion()"
    stats { metadata_id: 3 str_value:
      "jit(chunk)/while/body/repro.update/transpose(jvp(repro.update))/mul:"
    } } }
  event_metadata { key: 14 value { id: 14
    name: "%tree_sample.4 = f32[] custom-call()"
    stats { metadata_id: 3 str_value:
      "jit(chunk)/while/body/repro.replay.sample/jit(tree_sample)/pallas_call:"
    } } }
  event_metadata { key: 15 value { id: 15 name: "%copy.5 = f32[] copy()" } }
  stat_metadata { key: 1 value { id: 1 name: "run_id" } }
  stat_metadata { key: 2 value { id: 2 name: "_c" } }
  stat_metadata { key: 3 value { id: 3 name: "tf_op" } }
  stat_metadata { key: 4 value { id: 4
    name: "jit(chunk)/while/body/repro.replay.add/jit(replay_add)/scatter:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 880000 duration_ps: 180000 }
    events { metadata_id: 3 offset_ps: 1950000 duration_ps: 140000 }
  }
  lines { id: 2 name: "main" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 900000 duration_ps: 150000 }
    events { metadata_id: 5 offset_ps: 910000 duration_ps: 90000
      stats { metadata_id: 12 int64_value: 201 } }
    events { metadata_id: 4 offset_ps: 2100000 duration_ps: 50000 }
    events { metadata_id: 5 offset_ps: 2110000 duration_ps: 30000
      stats { metadata_id: 12 int64_value: 202 } }
  }
  lines { id: 3 name: "tfrt" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 1010000 duration_ps: 80000
      stats { metadata_id: 13 int64_value: 201 } }
    events { metadata_id: 7 offset_ps: 1020000 duration_ps: 20000
      stats { metadata_id: 11 int64_value: 1 }
      stats { metadata_id: 12 int64_value: -11 } }
    events { metadata_id: 6 offset_ps: 2150000 duration_ps: 40000
      stats { metadata_id: 13 int64_value: 202 } }
    events { metadata_id: 7 offset_ps: 2160000 duration_ps: 20000
      stats { metadata_id: 11 int64_value: 2 }
      stats { metadata_id: 12 int64_value: -22 } }
  }
  lines { id: 4 name: "callbacks" timestamp_ns: 0
    events { metadata_id: 8 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 9 offset_ps: 2000000 duration_ps: 50000
      stats { metadata_id: 11 int64_value: 1 }
      stats { metadata_id: 13 int64_value: -11 } }
    events { metadata_id: 9 offset_ps: CB2 duration_ps: 50000
      stats { metadata_id: 11 int64_value: 2 }
      stats { metadata_id: 13 int64_value: -22 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "repro.chunk_dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "repro.eval_readback" } }
  event_metadata { key: 4 value { id: 4
    name: "PJRT_LoadedExecutable_Execute" } }
  event_metadata { key: 5 value { id: 5 name: "tpu::System::Execute" } }
  event_metadata { key: 6 value { id: 6 name: "IssueSequencedEvent" } }
  event_metadata { key: 7 value { id: 7 name: "DoEnqueueProgram" } }
  event_metadata { key: 8 value { id: 8 name: "ReadSyncFlag" } }
  event_metadata { key: 9 value { id: 9 name: "CompleteCallbacks" } }
  stat_metadata { key: 11 value { id: 11 name: "run_id" } }
  stat_metadata { key: 12 value { id: 12 name: "_p" } }
  stat_metadata { key: 13 value { id: 13 name: "_c" } }
}
"""


def _xplane(tmp_path, text):
    from jax.profiler import ProfileData
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_synthetic_phases_idle_split_and_clock(tmp_path):
    path = _xplane(tmp_path, SYNTHETIC.replace("CB2", "2900000"))
    red = trace_scopes.reduce(path, WINDOW)
    assert red["clock_offset_s"] == pytest.approx([900e-9, 1100e-9])
    # the earlier reduction's keys, as trace_reduce gives them
    from jax.profiler import ProfileData
    old = trace_reduce.reduce(ProfileData.from_file(str(path)), WINDOW)
    assert {k: red[k] for k in old} == old
    assert red["busy_s"] == pytest.approx(1100e-9)
    # the while loop is a container; the copy carries no scope
    assert red["phases"] == pytest.approx({
        "repro.collect": 200e-9, "repro.replay.add": 200e-9,
        "repro.update": 200e-9, "repro.replay.sample": 300e-9,
        "unnamed": 200e-9})
    # window [0, 2000] on the device clock: idle inside the programs at
    # 300-400, 600-700 (scan body) and 1500-1600; between them at 0-100,
    # 900-1200 and 1800-2000
    assert red["idle_in_program_s"] == pytest.approx(300e-9)
    assert red["idle_between_s"] == pytest.approx(600e-9)
    assert red["idle_in_program_by_phase"] == pytest.approx({
        "repro.replay.add": 100e-9, "repro.update": 100e-9,
        "unnamed": 100e-9})
    # on the host clock: 1000-1100 in the dispatch span, 1900-2200 in the
    # readback span (a span before the shorter runtime event there),
    # 2800-3000 in the callback
    assert red["idle_between"] == pytest.approx({
        "repro.chunk_dispatch": 100e-9, "repro.eval_readback": 300e-9,
        "CompleteCallbacks": 200e-9})
    dev = red["scoped_devices"]["/device:TPU:0"]
    assert dev["clock_offset_s"] == red["clock_offset_s"]


def test_synthetic_empty_clock_bracket_raises(tmp_path):
    # the second callback before its program ends: hi = 200 < lo = 900
    path = _xplane(tmp_path, SYNTHETIC.replace("CB2", "2000000"))
    with pytest.raises(ValueError, match="clock bracket empty"):
        trace_scopes.reduce(path, WINDOW)


def test_one_op_with_two_scopes_raises(tmp_path):
    text = SYNTHETIC.replace("CB2", "2900000").replace(
        '"%copy.5 = f32[] copy()"', '"%fusion.1 = f32[] fusion()"')
    assert text != SYNTHETIC.replace("CB2", "2900000")
    with pytest.raises(ValueError, match="two scopes"):
        trace_scopes.op_scopes(_xplane(tmp_path, text))


def test_small_trace_keeps_the_earlier_keys():
    """Pinned from ``trace_reduce.reduce`` before the scoped reduction
    existed."""
    red = trace_scopes.reduce(SMALL, WINDOW)
    assert red["window_s"] == 0.010087811
    assert red["busy_s"] == 2.2743000000000002e-05
    assert red["ops"] == {
        "copy-start": 3.9000000000000005e-08,
        "copy-start.1": 6.000000000000001e-09,
        "copy-start.2": 6.000000000000001e-09, "copy-done.1": 8.96e-07,
        "copy.1": 6.160000000000001e-07, "copy-done.2": 7e-09,
        "tree_sample.1": 8.083e-06, "reduce": 1.3190000000000002e-06,
        "copy-done": 8e-09, "fusion": 1.1762999999999999e-05}
    assert red["gaps"] == {"idle": 1.2000000000000002e-08,
                           "$time sleep": 0.010065056}
    assert list(red["devices"]) == ["/device:TPU:0"]
    # the program's ops carry their name stacks; none is a repro phase
    scopes = trace_scopes.op_scopes(SMALL)["/device:TPU:0"]
    kernel = [v for k, v in scopes.items() if k.startswith("%tree_sample")]
    assert kernel == ["jit(step)/jit(sumtree_sample)/jit(tree_sample)/"
                      "pallas_call:"]
    assert red["phases"] == pytest.approx({"unnamed": red["busy_s"]})
    # each program starts 0.81-0.85 ms before its launch and ends 1.63-1.87
    # ms before its callbacks
    lo, hi = red["clock_offset_s"]
    assert lo == pytest.approx(0.848311e-3, abs=1e-9)
    assert hi == pytest.approx(1.632643e-3, abs=1e-9)


def test_cli_prints_the_tables(capsys):
    import json
    assert trace_scopes.main([str(SMALL), "--window", WINDOW]) == 0
    out, err = capsys.readouterr()
    assert json.loads(out)["clock_offset_s"] == pytest.approx(
        trace_scopes.reduce(SMALL, WINDOW)["clock_offset_s"])
    assert "phase unnamed" in err and "clock: host - device" in err


def test_without_plane_drops_only_that_plane(tmp_path):
    import record_scoped_trace
    path = tmp_path / "small.xplane.pb"
    path.write_bytes(record_scoped_trace.without_plane(
        SMALL.read_bytes(), "/host:metadata"))
    names = [p.name for p in trace_reduce.load(SMALL).planes]
    assert "/host:metadata" in names
    assert [p.name for p in trace_reduce.load(path).planes] == [
        n for n in names if n != "/host:metadata"]
    assert trace_scopes.reduce(path, WINDOW) == trace_scopes.reduce(
        SMALL, WINDOW)


@pytest.fixture(scope="module")
def scoped_path(tmp_path_factory):
    import gzip
    path = tmp_path_factory.mktemp("scoped") / "scoped_trace.xplane.pb"
    with gzip.open(SCOPED) as f:
        path.write_bytes(f.read())
    return path


@pytest.fixture(scope="module")
def scoped(scoped_path):
    return trace_scopes.reduce(scoped_path, WINDOW)


def test_scoped_trace_names_every_phase(scoped, scoped_path):
    assert set(PHASES) <= set(scoped["phases"])
    assert sum(scoped["phases"].values()) == pytest.approx(
        sum(scoped["ops"].values()), rel=1e-12)
    # The program's own ops outside the five phases (the PRNG split, the
    # loop counter) are a few percent at most. The ops XLA inserts at the
    # chunk's entry and exit (copies of the carried state) carry no name
    # stack at all, so no scope can name them: in ten-update chunks they
    # are about 15 % of the busy time (in the cell's 500-update chunks,
    # all unnamed ops together are about 1 %).
    scopes = trace_scopes.op_scopes(scoped_path)
    stray = sum(
        e.duration_ns * 1e-9
        for p in trace_reduce.load(scoped_path).planes
        if trace_reduce.DEVICE_PLANE.match(p.name)
        for line in p.lines if line.name == trace_reduce.OPS_LINE
        for e in line.events
        if scopes[p.name].get(e.name)
        and trace_scopes.phase_of(scopes[p.name][e.name]) == "unnamed"
        and not trace_reduce.CONTAINERS.match(trace_reduce.op_name(e.name)))
    assert 0 < stray < 0.03 * scoped["busy_s"]


def test_scoped_trace_idle_split_covers_the_aligned_window(scoped,
                                                          scoped_path):
    lo, hi = scoped["clock_offset_s"]
    assert lo <= hi
    off = 0.5 * (lo + hi) * 1e9
    pd = trace_reduce.load(scoped_path)
    (w0, w1), = [(e.start_ns, e.start_ns + e.duration_ns)
                 for p in pd.planes if p.name == trace_reduce.HOST_PLANE
                 for line in p.lines for e in line.events
                 if e.name == WINDOW]
    d0, d1 = w0 - off, w1 - off
    iv = [(max(e.start_ns, d0), min(e.start_ns + e.duration_ns, d1))
          for p in pd.planes if trace_reduce.DEVICE_PLANE.match(p.name)
          for line in p.lines if line.name == trace_reduce.OPS_LINE
          for e in line.events
          if not trace_reduce.CONTAINERS.match(trace_reduce.op_name(e.name))]
    busy, _ = trace_reduce.union_length(
        np.asarray([se for se in iv if se[1] > se[0]], np.float64))
    idle = scoped["idle_in_program_s"] + scoped["idle_between_s"]
    assert idle == pytest.approx((d1 - d0 - busy) * 1e-9, abs=1e-9)
    assert idle > 0
