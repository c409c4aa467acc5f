"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device busy time,
per-op device time and the host's activity in the device's idle gaps.

* The window is the extent of the host annotation named ``window`` (the
  harness wraps the traced stretch in one); without it, the span of all
  device ops.
* The device planes' clock is not the host's: on a v5e the first op of a
  stretch can read a millisecond before the host started it. Device
  intervals are shifted so that none starts before the window (no op
  runs before the host dispatched it), then clipped to the window.
* Busy time of a device is the union of its op intervals (the ``XLA Ops``
  line of each ``/device:TPU:<n>`` plane); the idle share is
  ``1 - busy / window``. Control-flow ops (``while``, ``conditional``,
  ``call``) span the ops they run and are left out, so the gaps between
  the ops of a scanned loop count as idle.
* Op time sums each op's durations by HLO instruction name (``fusion.3``,
  ``tree_sample.1``: the text between ``%`` and `` =`` of the event).
* Each idle gap is labelled with the shortest host event that covers its
  midpoint (what the host was doing while the device waited).
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def load(path) -> "object":
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_length(iv: np.ndarray) -> Tuple[float, np.ndarray]:
    """Total length of the union of ``(n, 2)`` intervals, and the merged
    intervals."""
    if len(iv) == 0:
        return 0.0, np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    m = np.asarray(merged, dtype=np.float64)
    return float(np.sum(m[:, 1] - m[:, 0])), m


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def reduce(pd, window: Optional[str] = None) -> dict:
    """The reduction of one loaded trace (see the module docstring)."""
    planes = list(pd.planes)
    host_ev: List[Tuple[str, float, float]] = []
    for p in planes:
        if p.name == HOST_PLANE:
            for line in p.lines:
                host_ev.extend(_events(line))
    dev: Dict[str, List[Tuple[str, float, float]]] = {}
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            for line in p.lines:
                if line.name == OPS_LINE:
                    dev.setdefault(p.name, []).extend(_events(line))
    if not dev:
        raise ValueError(f"no {OPS_LINE!r} line on a TPU device plane; "
                         f"planes: {[p.name for p in planes]}")

    win = [(s, e) for n, s, e in host_ev if window and n == window]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        w0 = min(s for evs in dev.values() for _, s, _ in evs)
        w1 = max(e for evs in dev.values() for _, _, e in evs)
    window_s = (w1 - w0) * 1e-9

    hosts = [(n, s, e) for n, s, e in host_ev if n != window]
    out = {"window_s": window_s, "devices": {}}
    for name, evs in sorted(dev.items()):
        shift = max(0.0, w0 - min(s for _, s, _ in evs)) if win else 0.0
        iv, ops = [], {}
        for n, s, e in evs:
            s, e = max(s + shift, w0), min(e + shift, w1)
            k = op_name(n)
            if e <= s or CONTAINERS.match(k):
                continue
            iv.append((s, e))
            ops[k] = ops.get(k, 0.0) + (e - s) * 1e-9
        busy, merged = union_length(np.asarray(iv, dtype=np.float64))
        out["devices"][name] = {"busy_s": busy * 1e-9, "ops": ops,
                                "gaps": _label_gaps(merged, w0, w1, hosts)}
    d = out["devices"].values()
    out["busy_s"] = float(np.mean([x["busy_s"] for x in d]))
    out["ops"] = {}
    out["gaps"] = {}
    for x in d:
        for k, v in x["ops"].items():
            out["ops"][k] = out["ops"].get(k, 0.0) + v / len(d)
        for k, v in x["gaps"].items():
            out["gaps"][k] = out["gaps"].get(k, 0.0) + v / len(d)
    return out


def _label_gaps(merged: np.ndarray, w0: float, w1: float,
                hosts) -> Dict[str, float]:
    """Idle seconds inside ``[w0, w1]`` by the host event that covers each
    gap's midpoint (``idle`` where none does)."""
    edges = [w0] + [x for se in merged for x in se] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    if not gaps:
        return {}
    hs = np.asarray([(s, e) for _, s, e in hosts], dtype=np.float64) \
        if hosts else np.zeros((0, 2))
    names = [n for n, _, _ in hosts]
    out: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label = "idle"
        if len(hs):
            cover = np.nonzero((hs[:, 0] <= mid) & (hs[:, 1] >= mid))[0]
            if len(cover):
                label = names[cover[np.argmin(hs[cover, 1] - hs[cover, 0])]]
        out[label] = out.get(label, 0.0) + (e - s) * 1e-9
    return out


def reduce_dir(trace_dir, window: Optional[str] = None) -> dict:
    return reduce(load(find_xplane(trace_dir)), window)
