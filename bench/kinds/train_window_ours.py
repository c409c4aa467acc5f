"""Training traffic for the paper's agent (OFENet with batch norm): the
``train_window`` kind, with its window, readings and comparison reused,
and two things replaced.

* The seeded weights: ``harness.make_params``, then every batch-norm
  ``scale`` and ``var`` at 1, as ``core/blocks.py``'s ``_bn_init`` starts
  them (zeros there would make the normalization degenerate).
* The reference: ``reference/ours_ref.py`` (OFENet's batch norm, the
  quadruped env).

For its per-layer metrics a ``--trace 1`` run reads the trace with
``trace_scopes.reduce`` (``trace_reduce``'s keys, per-phase time, the idle
split) and adds ``scopes``: device seconds per chip inside each of
``SCOPES`` at any depth of an op's name stack. The process keys its
compile cache on op metadata too, so a cached program keeps its scopes.

    python3 bench/kinds/train_window_ours.py --workload ours-u2048-train \\
        --seeds 1,2 --modes program,control,half_batch,half_batch.window

prints, on the chip, the readings the cell's limits are set from (as
``bench/readings.py`` does for ``train_window``).
"""
from __future__ import annotations

import contextlib
import gc
import json
import re
import shutil
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    _BENCH = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

import harness  # noqa: E402

tw = harness.kind_module("train_window")

SCOPES = ("repro.update", "repro.update.critic", "repro.update.actor",
          "repro.update.alpha", "repro.ofenet")
_MAKE_PARAMS = harness.make_params


def make_params(template, init_alpha: float, key):
    """``harness.make_params`` with batch norm's ``scale`` and ``var`` at 1."""
    import jax
    import jax.numpy as jnp

    def bn_init(path, x):
        keys = [getattr(k, "key", None) for k in path[-2:]]
        return jnp.ones_like(x) if keys in (["bn", "scale"], ["bn", "var"]) \
            else x
    return jax.tree_util.tree_map_with_path(
        bn_init, _MAKE_PARAMS(template, init_alpha, key))


@contextlib.contextmanager
def _seeded_weights():
    """``train_window.setup`` builds its seeded state with these weights."""
    harness.make_params = make_params
    try:
        yield
    finally:
        harness.make_params = _MAKE_PARAMS


def setup(ctx) -> dict:
    with _seeded_weights():
        return tw.setup(ctx)


def reference(ctx, dtype_name: str = "float32") -> dict:
    """``train_window.reference`` with these weights and ``ours_ref``."""
    import jax
    import jax.numpy as jnp
    from reference import ours_ref
    cfg, seed = ctx["config"], ctx["seed"]
    template = harness.params_template(cfg)
    params0 = jax.jit(lambda k: make_params(
        template, cfg["hyperparameters"]["init_alpha"], k))(
            harness.weights_key(seed))
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    prec = "highest" if dtype_name == "float32" else "default"
    with jax.default_matmul_precision(prec):
        steps = ours_ref.follow(cfg, params0, harness.seed31(seed), 3, dtype)
    return jax.device_get(tw.readings_of(
        params0, steps[0]["grads"], steps[2]["params"], steps[0]["metrics"],
        steps[0]["batch"], steps[0]["collected"], steps[0]["prio_total"]))


def scope_seconds(xplane, red: dict, names=SCOPES) -> dict:
    """Device seconds per chip of the ops in ``red["devices"]`` (the
    reduction's per-op time) whose name stack holds each scope of
    ``names`` as a whole component."""
    import trace_reduce
    import trace_scopes
    stacks = trace_scopes.op_scopes(xplane)
    pats = {n: re.compile(r"(?:^|[/(])" + re.escape(n) + r"(?:$|[/)])")
            for n in names}
    out = dict.fromkeys(names, 0.0)
    devs = red["devices"]
    for plane, d in devs.items():
        tf = {trace_reduce.op_name(ev): st
              for ev, st in stacks.get(plane, {}).items()}
        for op, sec in d["ops"].items():
            for n, pat in pats.items():
                if pat.search(tf.get(op, "")):
                    out[n] += sec / len(devs)
    return out


class ScopedTraceWindow(harness.TraceWindow):
    """The harness's trace window, reduced with the program's scopes."""

    def reduce(self):
        if not self.on:
            return None
        import trace_reduce
        import trace_scopes
        xplane = trace_reduce.find_xplane(self.dir)
        red = trace_scopes.reduce(xplane, window=self.NAME)
        red["scopes"] = scope_seconds(xplane, red)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def run(ctx) -> dict:
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    t0 = time.perf_counter()
    st = setup(ctx)
    setup_s = time.perf_counter() - t0
    harness.log(f"set-up {setup_s:.1f}s")
    tracer = ScopedTraceWindow(ctx["trace"], ctx["workload"])
    w = tw.window(ctx, st, ctx["seconds"], tracer)
    harness.log(f"window: {w['updates']} updates in {w['seconds']:.3f}s, "
                f"{w['evals']} evals, {w['compiles']} compiles inside the "
                f"window")
    device = harness.device_info(ctx["devices"])
    readings = st["readings"]
    del st
    gc.collect()
    red = tracer.reduce()
    numbers = tw.compare(readings, reference(ctx))
    ctx["run"] = {"updates_per_s": w["updates"] / w["seconds"], **w}
    return {"setup_s": setup_s, "device": device,
            "trace": red, "numbers": numbers,
            "attempted": w["updates"],
            "failed": 0 if w["finite"] else w["updates"],
            "end_to_end": {"updates_per_s": w["updates"] / w["seconds"]}}


def train_reading(ctx, mode: str) -> dict:
    """``readings.train_reading`` for this kind: the numbers ``compare``
    gives in ``mode`` (``program``, ``control``, a fault of
    ``bench/faults.py``)."""
    import faults
    if mode == "control":
        return tw.compare(reference(ctx, "bfloat16"), reference(ctx))
    with faults.planted(mode):
        st = setup(ctx)
    readings = st["readings"]
    del st
    gc.collect()
    return tw.compare(readings, reference(ctx))


def main(argv=None) -> int:
    import argparse
    import jax
    p = argparse.ArgumentParser(description="readings of the limits")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--modes", default="program,control")
    a = p.parse_args(argv)
    c = harness.cell(a.workload)
    devices = harness.require_chips(c["workload"]["chips"])
    harness.enable_cache()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    counter = harness.CompileCounter()
    for mode in a.modes.split(","):
        for seed in [int(s) for s in a.seeds.split(",")]:
            ctx = {**c, "seed": seed, "trace": False, "workload": a.workload,
                   "devices": devices, "compiles": counter}
            nums = train_reading(ctx, mode)
            print(json.dumps({"workload": a.workload, "mode": mode,
                              "seed": seed, "numbers": nums}), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
