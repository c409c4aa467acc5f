"""Share of the sum-tree kernels' roofline: the least time the bytes the
algorithm needs (``flops.sumtree_superstep_bytes``: one batch sample, the
batch's priority refresh and the actors' added rows, per update) take at
the HBM peak, over the summed device time of the sum-tree Pallas kernels
in the traced stretch, per chip. The kernels are custom calls named after
their jitted wrappers (``tree_sample.N``, ``tree_set_onehot.N``).
Memory-bound: the descent does no arithmetic worth a FLOP bound."""
import flops
import harness

KERNELS = ("tree_sample", "tree_set_onehot")


def kernel_seconds(ops):
    return sum(v for k, v in ops.items() if k.startswith(KERNELS))


def read(ctx):
    red, run = ctx.get("reduced"), ctx["run"]
    if not red or not run.get("traced_updates"):
        return None
    t = kernel_seconds(red["ops"])
    if t <= 0:
        return None
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    kind = ctx["devices"][0].device_kind
    if kind not in peaks:
        raise harness.BenchError(f"no peaks for device kind {kind!r}")
    shards = len(ctx["devices"])
    need = flops.sumtree_superstep_bytes(ctx["config"], shards) \
        * run["traced_updates"] / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * need / t
