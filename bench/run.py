"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s workload) names a configuration and a traffic
mix; the mix's ``kind`` picks ``bench/kinds/<kind>.py``. With ``--trace 0``
the line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window. The
run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import harness  # noqa: E402


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             devices=None) -> dict:
    """One run of the cell ``c`` (``harness.cell``). ``devices`` None: the
    cell's TPU chips (an error without them); tests pass the CPU device
    to drive the rest of a run."""
    workload = c["workload"]["name"]
    if devices is None:
        devices = harness.require_chips(c["workload"]["chips"])
    mod = harness.kind_module(c["traffic"]["kind"])
    ctx = {**c, "seed": seed, "seconds": seconds, "trace": trace,
           "workload": workload, "devices": devices,
           "compiles": harness.CompileCounter()}
    res = mod.run(ctx)
    verdict = harness.judge(res["numbers"], c["limits"])
    device = res["device"]
    if trace:
        red = res["trace"]
        device = dict(device, busy_s=red["busy_s"], window_s=red["window_s"])
        ctx["reduced"] = red
        metrics = harness.read_per_layer(c["per_layer"], ctx)
    else:
        metrics = {m["name"]: {"value": (res["setup_s"] if m["name"] == "setup_s"
                                         else res["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in c["end_to_end"]}
    out = {"correct": verdict["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {
            "device_ops": harness.top(res["trace"]["ops"]),
            "idle_gaps": harness.top(res["trace"]["gaps"])}
    return {"result": out, "checks": verdict["checks"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        c = harness.cell(a.workload)
        harness.enable_cache()
        r = run_cell(c, a.seed, a.seconds,
                     bool(a.trace))
    except (harness.BenchError, ImportError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.emit(r["result"], r["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
