"""``bench/trace_reduce.py`` on a small trace recorded on a v5e chip
(``bench/data/small_trace.xplane.pb``: three steps of a jitted matmul
chain and a Pallas sum-tree sample, each followed by a 2 ms host sleep
annotated ``host.sleep``, inside a ``bench.traced_window`` annotation)."""
from __future__ import annotations

import numpy as np
import pytest

import harness
import trace_reduce

TRACE = harness.BENCH / "data" / "small_trace.xplane.pb"
WINDOW = "bench.traced_window"


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(TRACE)


def _plane_events(pd):
    host, dev = [], []
    for p in pd.planes:
        for line in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if p.name == trace_reduce.HOST_PLANE:
                host += evs
            elif (trace_reduce.DEVICE_PLANE.match(p.name)
                  and line.name == trace_reduce.OPS_LINE):
                dev += evs
    return host, dev


def test_union_length():
    iv = np.array([[0, 2], [1, 3], [5, 6], [5.5, 5.7], [10, 10]], float)
    total, merged = trace_reduce.union_length(iv)
    assert total == 4.0
    assert merged.tolist() == [[0, 3], [5, 6], [10, 10]]
    assert trace_reduce.union_length(np.zeros((0, 2)))[0] == 0.0


SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 70000 duration_ps: 10000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = f32[] while()" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%tree_sample.3 = f32[] custom-call()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 40000 duration_ps: 30000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "host.work" } }
}
"""


def test_synthetic_loop():
    """A scanned loop: the ``while`` op spans its body, which leaves gaps."""
    from jax.profiler import ProfileData
    red = trace_reduce.reduce(ProfileData.from_text_proto(SYNTHETIC), WINDOW)
    assert red["window_s"] == pytest.approx(100e-9)
    # fusions 10-30 and 20-40 overlap: 30 ns; the kernel 70-80: 10 ns
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["ops"] == pytest.approx({"fusion.2": 40e-9,
                                        "tree_sample.3": 10e-9})
    # idle 0-10, 40-70 (the host worked 40-70), 80-100
    assert red["gaps"] == pytest.approx({"host.work": 30e-9, "idle": 30e-9})


def test_busy_idle_and_window(pd):
    red = trace_reduce.reduce(pd, WINDOW)
    host, dev = _plane_events(pd)
    (w0, w1), = [(s, e) for n, s, e in host if n == WINDOW]
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9, rel=1e-12)
    # the device clock reads the first op before the host's window began
    shift = w0 - min(s for _, s, _ in dev)
    assert 0 < shift < 2e6
    # busy time by a sweep over a 1 ns grid of the shifted op intervals
    grid = np.zeros(int(w1 - w0) + 1, bool)
    for _, s, e in dev:
        s, e = max(s + shift, w0), min(e + shift, w1)
        if e > s:
            grid[int(round(s - w0)):int(round(e - w0))] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-9, rel=1e-6)
    idle = 1 - red["busy_s"] / red["window_s"]
    # three 2 ms host sleeps sit inside the window with the device idle
    assert 3 * 2e-3 / red["window_s"] < idle < 1
    # and the idle gaps are put on what the host did then: sleeping
    slept = sum(v for k, v in red["gaps"].items() if "sleep" in k)
    assert slept >= 3 * 2e-3 * 0.9


def test_kernel_time_sums(pd):
    red = trace_reduce.reduce(pd, WINDOW)
    _, dev = _plane_events(pd)
    assert trace_reduce.op_name(dev[0][0]) in red["ops"]
    # the Pallas sum-tree sample is a custom call named after its wrapper
    kern = [(n, s, e) for n, s, e in dev if n.startswith("%tree_sample")]
    assert len(kern) == 3 and all("tpu_custom_call" in n for n, _, _ in kern)
    want = sum(e - s for _, s, e in kern) * 1e-9
    got = sum(v for k, v in red["ops"].items() if k.startswith("tree_sample"))
    assert got == pytest.approx(want, rel=1e-9)
    assert sum(red["ops"].values()) >= red["busy_s"] * (1 - 1e-9)
