"""Record ``bench/data/scoped_trace.xplane.pb.gz`` on one TPU chip: the scanned
training chunk of ``orig-u256-train``'s configuration, two chunks of ten
updates inside the benchmark's trace window, and print the phase table
``trace_scopes`` reads from it.

    python3 bench/record_scoped_trace.py

The cell's own configuration, not a tiny one: at tiny widths the per-update
bookkeeping outside the five phases (the PRNG split, the scan carry's
copies) is a fifth of the device time, at the cell's under 2 %. The
``/host:metadata`` plane (the compiled programs' HLO, which no reduction
reads) is left out, and the rest gzipped, to keep the file small.
"""
from __future__ import annotations

import gzip
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402

OUT = BENCH / "data" / "scoped_trace.xplane.pb.gz"
STEPS = 10
DROP = "/host:metadata"


def without_plane(data: bytes, name: str) -> bytes:
    """The serialized XSpace ``data`` without the plane called ``name``
    (every top-level field of an XSpace is length-delimited)."""
    out, i = [], 0
    while i < len(data):
        start = i
        key, i = trace_scopes._varint(data, i)
        ln, i = trace_scopes._varint(data, i)
        body, i = data[i:i + ln], i + ln
        if key >> 3 == 1 and any(f == 2 and bytes(v).decode() == name
                                 for f, v in trace_scopes._fields(body)):
            continue
        out.append(data[start:i])
    return b"".join(out)


def main() -> int:
    import jax
    from repro.rl.experiment import Experiment, ExperimentSpec
    try:
        harness.require_chips(1)
    except harness.BenchError as e:
        print(f"record_scoped_trace: {e}", file=sys.stderr)
        return 1
    spec = harness.cell("orig-u256-train")["config"]["spec"]
    exp = Experiment.from_spec(ExperimentSpec.from_dict(spec))
    exp.run(STEPS)                                  # compiles the chunk
    jax.block_until_ready(exp._ls)
    tw = harness.TraceWindow(True, "scoped_trace")
    with tw:
        for _ in range(2):
            exp.run(STEPS)
        jax.block_until_ready(exp._ls)
    xplane = trace_reduce.find_xplane(tw.dir)
    with gzip.open(OUT, "wb") as f:
        f.write(without_plane(xplane.read_bytes(), DROP))
    print(f"{OUT.name}: {OUT.stat().st_size} bytes", file=sys.stderr)
    print(trace_scopes.table(trace_scopes.reduce(xplane, tw.NAME)),
          file=sys.stderr)
    shutil.rmtree(tw.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
