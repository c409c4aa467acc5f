"""Model FLOP utilization of the training superstep: model FLOPs per
update (``bench/flops.py``) times the updates completed in the traced
stretch, over the stretch's length times the chips' bf16 peak
(``bench/peaks.json``). XLA:TPU runs the float32 matmuls at DEFAULT
precision as one bf16 pass, so bf16 is the peak they run against."""
import flops
import harness


def read(ctx):
    red, run = ctx.get("reduced"), ctx["run"]
    if not red or not run.get("traced_updates"):
        return None
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    kind = ctx["devices"][0].device_kind
    if kind not in peaks:
        raise harness.BenchError(f"no peaks for device kind {kind!r}")
    f = flops.superstep_flops(ctx["config"]) * run["traced_updates"]
    return 100.0 * f / (red["window_s"] * len(ctx["devices"])
                        * peaks[kind]["bf16_flops_per_s"])
