"""Model FLOP utilization of the SAC (+ OFENet) update alone: its model
FLOPs per update (``flops.update_flops``) times the updates of the traced
stretch, over the device time of the ops inside the ``repro.update`` scope
(the kind's ``scopes``, per chip) times the chips' bf16 peak. The wide
trunk's share of its roofline; None where the trace carries no scopes."""
import flops
import harness


def read(ctx):
    red, run = ctx.get("reduced"), ctx["run"]
    if not red or not run.get("traced_updates"):
        return None
    t = red.get("scopes", {}).get("repro.update", 0.0)
    if t <= 0:
        return None
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    kind = ctx["devices"][0].device_kind
    if kind not in peaks:
        raise harness.BenchError(f"no peaks for device kind {kind!r}")
    f = flops.update_flops(ctx["config"]) * run["traced_updates"]
    return 100.0 * f / (t * len(ctx["devices"])
                        * peaks[kind]["bf16_flops_per_s"])
