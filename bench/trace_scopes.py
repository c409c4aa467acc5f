"""Where a traced stretch's device time goes by the program's own names,
and whether the idle device waited on its own program or on the host.

``reduce`` returns every key of ``trace_reduce.reduce`` unchanged, and adds:

* ``phases``: device seconds per phase. Each op's event metadata carries
  the JAX name stack it was traced under (its ``tf_op`` stat); the op's
  phase is the outermost ``repro.*`` component there (the ``Trainer``'s
  ``jax.named_scope`` per superstep phase), else ``unnamed``. Durations
  are counted as ``ops`` counts them (shifted, clipped, containers out).
* ``clock_offset_s``: ``[lo, hi]``, the bracket of host clock minus device
  clock. A program execution (``XLA Modules`` line) starts after the host
  launch that caused it (``PJRT_LoadedExecutable_Execute``, found by
  following the trace's flow links back from the program's enqueue) and
  ends before its ``CompleteCallbacks`` (same ``run_id`` and ``_c``): ``lo``
  is the largest launch-minus-start, ``hi`` the smallest callback-minus-end.
* ``idle_in_program_s`` and ``idle_between_s``: the device's idle time in
  the window, moved onto the device clock by the bracket's midpoint, split
  by the program executions: idle while a program runs (between its ops)
  and idle between programs (the device waiting on the host).
  ``idle_in_program_by_phase`` puts each in-program gap on the phase of the
  op that ends it; ``idle_between`` labels each between-program gap with
  the innermost ``repro.*`` or ``bench.*`` host span over its midpoint (on
  the host clock), else the shortest host event there, else ``idle``.

The ``tf_op`` stats are read from the ``.xplane.pb`` by a small reader of
the protobuf wire format (``jax.profiler.ProfileData`` does not expose the
stats of event metadata).

    python3 bench/trace_scopes.py <trace dir or .xplane.pb> [--window NAME]

prints these tables for any ``jax.profiler`` trace, such as an
``obs.trace`` capture.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import trace_reduce

MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
CALLBACK = "CompleteCallbacks"
UNNAMED = "unnamed"
PHASE = re.compile(r"(?:^|[/(])(repro\.[\w.]+)")
SPAN = re.compile(r"^(repro|bench)\.")


# ------------------------------------------------------ protobuf wire format
def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one message: ints for varints, the
    bytes of a length-delimited field (a nested message or a string)."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, v


def _map_values(b):
    for f, v in _fields(b):
        if f == 2:
            return v
    return b""


def op_scopes(path) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: tf_op}}`` from an ``.xplane.pb``.
    Field numbers are those of ``xplane.proto``: XSpace.planes 1; XPlane
    name 2, event_metadata 4, stat_metadata 5; XEventMetadata name 2,
    stats 5; XStat metadata_id 1, str_value 5, ref_value 7 (a string
    interned as the name of a stat metadata)."""
    out = {}
    data = memoryview(Path(path).read_bytes())
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 4:
                events.append(_map_values(pv))
            elif pf == 5:
                meta = dict(_fields(_map_values(pv)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        scopes: Dict[str, str] = {}
        for ev in events:
            ev_name, tf_op = "", ""
            for ef, evv in _fields(ev):
                if ef == 2:
                    ev_name = bytes(evv).decode()
                elif ef == 5:
                    stat = dict(_fields(evv))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    tf_op = (bytes(stat[5]).decode() if 5 in stat
                             else stat_names.get(stat.get(7), ""))
            if scopes.setdefault(ev_name, tf_op) != tf_op:
                raise ValueError(f"{name}: op {ev_name[:80]!r} carries two "
                                 f"scopes, {scopes[ev_name]!r} and {tf_op!r}")
        out[name] = scopes
    return out


def phase_of(tf_op: str) -> str:
    """The outermost ``repro.*`` component of a name stack."""
    m = PHASE.search(tf_op)
    return m.group(1) if m else UNNAMED


# ------------------------------------------------------------- host events
class HostEvent(NamedTuple):
    name: str
    line: int
    start: float
    end: float
    stats: dict


def host_events(pd) -> List[HostEvent]:
    out = []
    for p in pd.planes:
        if p.name == trace_reduce.HOST_PLANE:
            for i, line in enumerate(p.lines):
                out.extend(HostEvent(e.name, i, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats)) for e in line.events)
    return out


def _launch_of(flow: int, host: List[HostEvent], by_producer) \
        -> Optional[HostEvent]:
    """The host launch behind the device program that consumes ``flow``:
    walk back from the flow's producers (the program's enqueue) through
    the events enclosing each on its thread and the producers of their
    own flows, nearest first."""
    front, seen = list(by_producer.get(flow, ())), set()
    while front:
        nxt = []
        for ev in front:
            if id(ev) in seen:
                continue
            seen.add(id(ev))
            if ev.name == LAUNCH:
                return ev
            nxt += [h for h in host if h.line == ev.line and h is not ev
                    and h.start <= ev.start and h.end >= ev.end]
            if "_c" in ev.stats:
                nxt += by_producer.get(ev.stats["_c"], ())
        front = nxt
    return None


def clock_bracket(modules, host: List[HostEvent]) -> Tuple[float, float]:
    """``(lo, hi)`` in ns of host clock minus device clock, from the
    program executions ``modules`` (``(start, end, stats)``, device clock)
    paired with their launches and callbacks."""
    by_producer = defaultdict(list)
    for h in host:
        if "_p" in h.stats:
            by_producer[h.stats["_p"]].append(h)
    done = {(h.stats.get("run_id"), h.stats.get("_c")): h.start
            for h in host if h.name == CALLBACK}
    los, his = [], []
    for s, e, st in modules:
        launch = _launch_of(st.get("_c"), host, by_producer)
        if launch is not None:
            los.append(launch.start - s)
        cb = done.get((st.get("run_id"), st.get("_c")))
        if cb is not None:
            his.append(cb - e)
    if not los or not his:
        raise ValueError("no program execution is paired with its host "
                         "launch and its callbacks")
    lo, hi = max(los), min(his)
    if lo > hi:
        raise ValueError(f"clock bracket empty: host - device offset "
                         f">= {lo * 1e-6:.4f} ms and <= {hi * 1e-6:.4f} ms")
    return lo, hi


# --------------------------------------------------------------- reduction
def _minus(gaps: np.ndarray, cover: np.ndarray):
    """Split each interval of ``gaps`` into its pieces inside and outside
    the merged intervals ``cover``: two lists of ``(s, e)``."""
    inside, outside = [], []
    for s, e in gaps:
        t = s
        for cs, ce in cover:
            if ce <= t or cs >= e:
                continue
            if cs > t:
                outside.append((t, cs))
            inside.append((max(cs, t), min(ce, e)))
            t = min(ce, e)
        if t < e:
            outside.append((t, e))
    return inside, outside


def _span_label(s: float, e: float, hosts: List[HostEvent]) -> str:
    mid = 0.5 * (s + e)
    cover = [h for h in hosts if h.start <= mid <= h.end]
    spans = [h for h in cover if SPAN.match(h.name)]
    pick = spans or cover
    if not pick:
        return "idle"
    return min(pick, key=lambda h: h.end - h.start).name


def _device(ops, modules, scopes, host, hosts, window, w0, w1) -> dict:
    """The additions for one device plane: ``ops`` ``(name, s, e)`` and
    ``modules`` ``(s, e, stats)`` on the device clock, ``hosts`` the host
    events labels are taken from, ``[w0, w1]`` the host's window."""
    # phases: the shift and clipping trace_reduce gives ``ops``
    shift = max(0.0, w0 - min(s for _, s, _ in ops)) if window else 0.0
    phases: Dict[str, float] = defaultdict(float)
    for n, s, e in ops:
        s, e = max(s + shift, w0), min(e + shift, w1)
        if e > s and not trace_reduce.CONTAINERS.match(
                trace_reduce.op_name(n)):
            phases[phase_of(scopes.get(n, ""))] += (e - s) * 1e-9

    lo, hi = clock_bracket(modules, host)
    off = 0.5 * (lo + hi)
    # the window on the device clock (without a host window: the ops' span)
    d0, d1 = (w0 - off, w1 - off) if window else (w0, w1)
    body = sorted((max(s, d0), min(e, d1), n) for n, s, e in ops
                  if not trace_reduce.CONTAINERS.match(
                      trace_reduce.op_name(n))
                  and min(e, d1) > max(s, d0))
    _, busy = trace_reduce.union_length(
        np.asarray([(s, e) for s, e, _ in body], np.float64).reshape(-1, 2))
    edges = [d0] + [x for se in busy for x in se] + [d1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    _, run = trace_reduce.union_length(np.asarray(
        [(max(s, d0), min(e, d1)) for s, e, _ in modules
         if min(e, d1) > max(s, d0)], np.float64).reshape(-1, 2))
    inside, outside = _minus(gaps, run)

    starts = np.asarray([s for s, _, _ in body])
    by_phase: Dict[str, float] = defaultdict(float)
    for s, e in inside:
        i = int(np.searchsorted(starts, e))
        nxt = phase_of(scopes.get(body[i][2], "")) if i < len(body) \
            else UNNAMED
        by_phase[nxt] += (e - s) * 1e-9
    between: Dict[str, float] = defaultdict(float)
    for s, e in outside:
        between[_span_label(s + off, e + off, hosts)] += (e - s) * 1e-9
    return {"phases": dict(phases),
            "idle_in_program_s": sum((e - s) for s, e in inside) * 1e-9,
            "idle_between_s": sum((e - s) for s, e in outside) * 1e-9,
            "idle_in_program_by_phase": dict(by_phase),
            "idle_between": dict(between),
            "clock_offset_s": [lo * 1e-9, hi * 1e-9]}


def reduce(path, window: Optional[str] = None) -> dict:
    """``trace_reduce.reduce`` of the ``.xplane.pb`` at ``path``, with the
    additions of the module docstring as the mean over the devices (the
    clock bracket: the hull of the devices' brackets) and per device
    (``scoped_devices``)."""
    pd = trace_reduce.load(path)
    out = trace_reduce.reduce(pd, window)
    scopes = op_scopes(path)
    host = host_events(pd)
    win = [(h.start, h.end) for h in host if window and h.name == window]
    hosts = [h for h in host if h.name != window]
    ops, modules = {}, {}
    for p in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(p.name):
            for line in p.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops[p.name] = [(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[p.name] = [(e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        dict(e.stats)) for e in line.events]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        w0 = min(s for evs in ops.values() for _, s, _ in evs)
        w1 = max(e for evs in ops.values() for _, _, e in evs)
    per = {name: _device(evs, modules.get(name, []), scopes.get(name, {}),
                         host, hosts, bool(win), w0, w1)
           for name, evs in sorted(ops.items())}
    out["scoped_devices"] = per
    n = len(per)
    for key in ("phases", "idle_in_program_by_phase", "idle_between"):
        acc: Dict[str, float] = defaultdict(float)
        for add in per.values():
            for k, v in add[key].items():
                acc[k] += v / n
        out[key] = dict(acc)
    for key in ("idle_in_program_s", "idle_between_s"):
        out[key] = float(np.mean([add[key] for add in per.values()]))
    out["clock_offset_s"] = [min(a["clock_offset_s"][0] for a in per.values()),
                             max(a["clock_offset_s"][1] for a in per.values())]
    return out


def reduce_dir(trace_dir, window: Optional[str] = None) -> dict:
    return reduce(trace_reduce.find_xplane(trace_dir), window)


def table(red: dict) -> str:
    """The phase table, the idle split with its labels and the clock
    bracket, per chip, as text."""
    busy, win = red["busy_s"], red["window_s"]
    rows = [f"window {win * 1e3:.3f} ms, busy {busy * 1e3:.3f} ms"]
    for k, v in sorted(red["phases"].items(), key=lambda kv: -kv[1]):
        rows.append(f"  phase {k}: {v * 1e3:.3f} ms "
                    f"({100 * v / busy:.2f} % of busy)")
    rows.append(f"idle in program {red['idle_in_program_s'] * 1e3:.3f} ms "
                f"({100 * red['idle_in_program_s'] / win:.3f} % of window)")
    for k, v in sorted(red["idle_in_program_by_phase"].items(),
                       key=lambda kv: -kv[1]):
        rows.append(f"  before {k}: {v * 1e3:.3f} ms")
    rows.append(f"idle between programs {red['idle_between_s'] * 1e3:.3f} "
                f"ms ({100 * red['idle_between_s'] / win:.3f} % of window)")
    for k, v in sorted(red["idle_between"].items(), key=lambda kv: -kv[1]):
        rows.append(f"  in {k}: {v * 1e3:.3f} ms")
    lo, hi = red["clock_offset_s"]
    rows.append(f"clock: host - device in [{lo * 1e3:.4f}, {hi * 1e3:.4f}] "
                f"ms")
    return "\n".join(rows)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace", help="a trace directory or an .xplane.pb")
    p.add_argument("--window", default=None,
                   help="the host span that bounds the stretch")
    a = p.parse_args(argv)
    path = Path(a.trace)
    red = (reduce_dir if path.is_dir() else reduce)(path, a.window)
    print(table(red), file=sys.stderr)
    print(json.dumps({k: red[k] for k in (
        "window_s", "busy_s", "phases", "idle_in_program_s",
        "idle_between_s", "idle_in_program_by_phase", "idle_between",
        "clock_offset_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
