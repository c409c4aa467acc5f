#!/usr/bin/env python3
"""Drive the main training and serving paths once on a TPU.

    python chip_smoke.py            # one chip: every phase below
    python chip_smoke.py --mesh4    # four chips: mesh training vs one shard

Everything runs in this one process (a chip belongs to one process), through
the entry points a user calls: ``Experiment``, ``Policy`` and
``PolicyServer``. The configuration is ``table1-ours`` at the paper budget
(``benchmarks/common.make_spec("paper", "table1-ours")``: DenseNet trunk,
OFENet 64x4, Ape-X pool 2x16, batch 256, replay 100 000) at the widest
paper width, ``network.num_units=2048`` (fig3). Only the step budget is
cut; the cuts are printed. Weights and data come from fixed seeds.

One-chip phases, one line each (compile seconds are host-side compile time,
not device time):

* ``train_tpu``     scan loop + device replay + Pallas sum-tree, with a
                    one-chunk profiler trace. Checks: finite returns and
                    params, Mosaic kernels (``tpu_custom_call``) in the
                    compiled chunk, a TPU plane in the trace, and the
                    sum-tree kernels equal to the XLA reference on the
                    trained tree.
* ``parity``        one SAC update on the TPU and on the host CPU, same
                    inputs; relative gaps in losses and grad norms within
                    the bound of the matmul precision the program runs at.
* ``fused``         a training segment with ``block_backend="fused"`` at the
                    widest trunk that compiles for the chip (U=512), and
                    one update compared with ``block_backend="jnp"``.
* ``serve``         a ``PolicyServer`` answering client threads; every
                    answer equals ``Policy.act_deterministic`` on the same
                    observation at the server's batch shape.

``--mesh4`` runs only ``mesh4``: the train_tpu spec with
``execution.mesh_shards=4`` next to the same spec on one chip. Checks: each
of the 4 devices holds a quarter of the replay rows, the sharded ops ran,
returns are finite and close to the one-chip run.

The last line of standard output is one JSON object naming the device. The
script exits nonzero, without that line, when JAX finds no TPU or when any
phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "experiments" / "chip_smoke"   # gitignored artifacts

WIDTH = 2048                  # widest fig3 width (benchmarks/fig3_width.py)
# budget cuts of the paper settings (benchmarks/common.PAPER); nothing else
# in the spec changes
STEPS = 300                   # total_steps 1_000_000
WARMUP = 256                  # warmup_steps 10_000: one batch of rows
EVAL_EVERY = 100              # eval.every 10_000: three eval points
FUSED_STEPS = 20
# widest trunk whose fused-stack training chunk compiles for v5e: at U=1024
# the stack's backward exceeds the kernel's 16 MiB scoped-VMEM limit
FUSED_WIDTH = 512
SERVE_REQUESTS = 256
SERVE_CLIENTS = 8
# XLA:TPU runs float32 matmuls at DEFAULT precision as one bfloat16 pass:
# each operand is rounded to 8 significant bits (unit roundoff 2**-9), so
# one matmul carries a relative error of about 2 * 2**-9 = 2**-8. The
# deepest path in one SAC update chains 11 matmuls (OFENet state block 4,
# state-action block 4, trunk 2, head 1); first-order errors add along the
# chain: 11 * 2**-8 ~= 0.043. The losses are means over the batch of
# squared-ish errors, so the bound is twice that.
PRECISION_BOUND = 2 * 11 * 2.0 ** -8


def _fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(code)


class CheckFailed(RuntimeError):
    """A phase produced a wrong or missing result."""


def _require(ok, what) -> None:
    """A result check (kept under ``python -O``, unlike ``assert``)."""
    if not ok:
        raise CheckFailed(what)


def _line(name: str, **fields) -> None:
    parts = []
    for k, v in fields.items():
        if isinstance(v, float):
            v = f"{v:.6g}"
        elif isinstance(v, (list, tuple)):
            v = "[" + ",".join(f"{x:.6g}" if isinstance(x, float) else str(x)
                               for x in v) + "]"
        parts.append(f"{k}={v}")
    print(f"phase={name} " + " ".join(parts), flush=True)


# ------------------------------------------------------------------- specs

def paper_spec(**overrides):
    """table1-ours at the paper budget and U=2048, cut to the smoke budget,
    on the TPU path (Pallas sum-tree)."""
    from benchmarks.common import make_spec
    return make_spec("paper", "table1-ours", **{
        "network.num_units": WIDTH, "replay.kernel": "pallas",
        "execution.total_steps": STEPS, "execution.warmup_steps": WARMUP,
        "eval.every": EVAL_EVERY, **overrides})


def cuts_line(spec) -> str:
    from benchmarks.common import PAPER
    x = spec.execution
    return (f"cut: total_steps {PAPER['total_steps']}->{x.total_steps}, "
            f"warmup_steps {PAPER['warmup_steps']}->{x.warmup_steps}, "
            f"eval.every {PAPER['eval_every']}->{spec.eval.every}; "
            f"unchanged: num_units={spec.network.num_units} "
            f"num_layers={spec.network.num_layers} "
            f"connectivity={spec.network.connectivity} "
            f"ofenet={spec.ofenet.num_units}x{spec.ofenet.num_layers} "
            f"actors={x.n_actors} batch={x.batch_size} "
            f"replay={spec.replay.capacity} "
            f"eval.episodes={spec.eval.episodes}")


# ------------------------------------------------------------------ checks

def _finite_tree(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree_util.tree_leaves(jax.device_get(tree)))


def check_mosaic(hlo: str) -> int:
    """Number of Mosaic kernels in a compiled program; fails on none."""
    n = hlo.count('custom_call_target="tpu_custom_call"')
    _require(n > 0, "no tpu_custom_call in the compiled program")
    return n


def check_trace(trace_dir: Path) -> str:
    """The profiler trace holds a TPU device plane."""
    import jax
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    _require(files, f"no .xplane.pb under {trace_dir}")
    planes = [p.name for p in
              jax.profiler.ProfileData.from_file(str(files[-1])).planes]
    tpu = [p for p in planes if p.startswith("/device:TPU")]
    _require(tpu, f"no TPU device plane in the trace: {planes}")
    return ",".join(tpu)


def rel_gaps(a: dict, b: dict, keys) -> dict:
    return {k: abs(float(a[k]) - float(b[k]))
            / max(abs(float(b[k])), 1e-12) for k in keys}


# ------------------------------------------------------------------ phases

def phase_train_tpu(ctx) -> None:
    import jax
    import numpy as np
    from repro.kernels.replay_tree import ops as rt
    from repro.rl import Experiment

    shutil.rmtree(OUT / "trace", ignore_errors=True)    # <log_dir>/trace
    spec = ctx["spec"].override(**{"obs.enabled": True, "obs.trace": 1,
                                   "obs.log_dir": str(OUT)})
    print(cuts_line(spec), flush=True)
    exp = Experiment.from_spec(spec)
    t0 = time.time()
    exp._ensure_init()
    jax.block_until_ready(exp._ls)
    init_s = time.time() - t0
    chunk = exp.trainer.chunk_fn(EVAL_EVERY, True, False)
    t0 = time.time()
    compiled = chunk.__wrapped__.lower(exp._ls).compile()
    compile_s = time.time() - t0
    kernels = check_mosaic(compiled.as_text())
    t0 = time.time()
    res = exp.run(spec.execution.total_steps)
    jax.block_until_ready(exp._ls)
    wall_s = time.time() - t0
    exp.close()
    _require(len(res.returns) == STEPS // EVAL_EVERY, res.returns)
    _require(np.isfinite(res.returns).all(), res.returns)
    _require(_finite_tree(exp._ls.agent["params"]), "non-finite params")
    status = exp.obs.trace.status
    _require(status == "done", f"trace status {status!r}")
    planes = check_trace(Path(exp.obs.trace.trace_dir))

    # the Pallas sum-tree on the trained tree == the XLA reference
    cfg = exp.trainer.dcfg
    tree = exp._ls.replay["tree"]
    total = float(rt.sumtree_total(tree))
    key = jax.random.key(7)
    targets = jax.random.uniform(key, (256,), maxval=total)
    interp = cfg.interpret
    leaf_p, pri_p = rt.sumtree_sample(tree, targets, capacity=cfg.capacity,
                                      backend="pallas", interpret=interp)
    leaf_x, pri_x = rt.sumtree_sample(tree, targets, capacity=cfg.capacity,
                                      backend="xla")
    same_leaf = float(np.mean(np.asarray(leaf_p) == np.asarray(leaf_x)))
    _require(same_leaf == 1.0, f"pallas sample leaves differ ({same_leaf})")
    np.testing.assert_array_equal(np.asarray(pri_p), np.asarray(pri_x))
    idx = jax.random.randint(jax.random.key(8), (256,), 0, cfg.capacity)
    vals = jax.random.uniform(jax.random.key(9), (256,), minval=0.1,
                              maxval=2.0)
    set_p = rt.sumtree_set(tree, idx, vals, backend="pallas",
                           interpret=interp)
    set_x = rt.sumtree_set(tree, idx, vals, backend="xla")
    set_gap = float(np.max(np.abs(np.asarray(set_p) - np.asarray(set_x)))
                    / max(total, 1e-12))
    _require(set_gap < 1e-5, f"pallas set differs from xla by {set_gap}")

    ctx["exp"] = exp
    _line("train_tpu", ok=True, init_compile_s=init_s,
          chunk_compile_s=compile_s, mosaic_kernels=kernels,
          steps=int(exp.step), host_wall_s=wall_s,
          returns=[float(r) for r in res.returns],
          params=res.param_count, trace=status, trace_planes=planes,
          sumtree_sample_equal=same_leaf, sumtree_set_rel_gap=set_gap)


def _update_inputs(exp):
    """The run's agent state and a batch of its replay rows, on host."""
    import jax
    data = exp._ls.replay["store"]["data"]
    n = exp.spec.execution.batch_size
    batch = {k: v[:n] for k, v in data.items()}
    batch["weight"] = jax.numpy.ones((n,), jax.numpy.float32)
    return jax.device_get(exp._ls.agent), jax.device_get(batch)


def _trained(ctx):
    """The train_tpu experiment, or a freshly initialized one if that
    phase failed (so later phases still report)."""
    from repro.rl import Experiment
    if "exp" not in ctx:
        exp = Experiment.from_spec(ctx["spec"])
        exp._ensure_init()
        ctx["exp"] = exp
    return ctx["exp"]


def _one_update(acfg, agent, batch, device):
    import jax
    from repro.rl.sac import sac_update
    agent, batch = jax.device_put((agent, batch), device)
    fn = jax.jit(lambda st, b, k: sac_update(st, acfg, b, k))
    t0 = time.time()
    compiled = fn.lower(agent, batch, jax.random.key(11)).compile()
    compile_s = time.time() - t0
    _, m = compiled(agent, batch, jax.random.key(11))
    scalars = jax.device_get({k: v for k, v in m.items() if v.ndim == 0})
    return scalars, compile_s, compiled.as_text()


_LOSSES = ("critic_loss", "actor_loss", "aux_loss")
_GRADS = ("grad_norm_critics", "grad_norm_actor", "grad_norm_ofenet")


def phase_parity(ctx) -> None:
    import dataclasses

    import jax
    exp = _trained(ctx)
    agent, batch = _update_inputs(exp)
    acfg = dataclasses.replace(exp.trainer.acfg, grad_norms=True)
    chip, compile_chip, _ = _one_update(acfg, agent, batch, ctx["device"])
    host, compile_host, _ = _one_update(acfg, agent, batch,
                                        jax.devices("cpu")[0])
    gaps = rel_gaps(chip, host, _LOSSES + _GRADS)
    worst = max(gaps.values())
    precision = jax.config.jax_default_matmul_precision or "DEFAULT"
    _line("parity", ok=worst <= PRECISION_BOUND, matmul_precision=precision,
          bound=PRECISION_BOUND, worst_rel_gap=worst,
          compile_s_chip=compile_chip, compile_s_cpu=compile_host,
          **{f"gap_{k}": v for k, v in gaps.items()})
    _require(worst <= PRECISION_BOUND, gaps)


def phase_fused(ctx) -> None:
    import dataclasses

    import jax
    import numpy as np
    from repro.rl import Experiment
    from repro.rl.sac import mean_action

    spec = ctx["spec"].override(**{
        "network.block_backend": "fused", "network.num_units": FUSED_WIDTH,
        "execution.total_steps": FUSED_STEPS, "eval.every": FUSED_STEPS})
    exp = Experiment.from_spec(spec)
    t0 = time.time()
    res = exp.run(FUSED_STEPS)
    jax.block_until_ready(exp._ls)
    wall_s = time.time() - t0
    _require(np.isfinite(res.returns).all(), res.returns)
    _require(_finite_tree(exp._ls.agent["params"]), "non-finite params")

    # one forward + backward: fused vs jnp, same params and batch
    agent, batch = _update_inputs(exp)
    fcfg = dataclasses.replace(exp.trainer.acfg, grad_norms=True)
    jcfg = dataclasses.replace(fcfg, block_backend="jnp")
    dev = ctx["device"]
    fused, compile_fused, hlo = _one_update(fcfg, agent, batch, dev)
    kernels = check_mosaic(hlo)
    ref, _, _ = _one_update(jcfg, agent, batch, dev)
    gaps = rel_gaps(fused, ref, _LOSSES + _GRADS)
    params, obs = jax.device_put((agent["params"], batch["obs"]), dev)
    a_f = jax.jit(lambda p, o: mean_action(p, fcfg, o))(params, obs)
    a_j = jax.jit(lambda p, o: mean_action(p, jcfg, o))(params, obs)
    fwd_gap = float(np.max(np.abs(np.asarray(a_f) - np.asarray(a_j))))
    worst = max(gaps.values())
    _line("fused", ok=worst <= PRECISION_BOUND and fwd_gap <= PRECISION_BOUND,
          units=spec.network.num_units, layers=spec.network.num_layers,
          steps=int(exp.step), host_wall_s_with_compile=wall_s,
          returns=[float(r) for r in res.returns],
          update_compile_s=compile_fused, update_mosaic_kernels=kernels,
          action_max_abs_gap=fwd_gap,
          worst_rel_gap=worst, bound=PRECISION_BOUND,
          **{f"gap_{k}": v for k, v in gaps.items()})
    _require(worst <= PRECISION_BOUND and fwd_gap <= PRECISION_BOUND,
             (gaps, fwd_gap))


def phase_serve(ctx) -> None:
    import numpy as np
    from repro.launch.serve_policy import PolicyServer, ServeConfig

    policy = _trained(ctx).policy()
    cfg = ServeConfig(max_batch=32, max_wait_ms=2.0)
    t0 = time.time()
    for slot in cfg.batch_slots:          # compile every batch slot first
        np.asarray(policy.act_deterministic(
            np.zeros((slot, policy.obs_dim), np.float32)))
    np.asarray(policy.act_deterministic(np.zeros(policy.obs_dim,
                                                 np.float32)))
    compile_s = time.time() - t0
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((SERVE_REQUESTS, policy.obs_dim)).astype(
        np.float32)
    answers = [None] * SERVE_REQUESTS
    errors = []

    def client(c):
        try:
            for i in range(c, SERVE_REQUESTS, SERVE_CLIENTS):
                answers[i] = server.submit(obs[i], timeout=120.0)
        except BaseException as e:      # reported below
            errors.append(repr(e))

    server = PolicyServer(policy, cfg).start()
    t0 = time.time()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(SERVE_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.time() - t0
    server.close()
    _require(not errors, errors)
    got = np.stack(answers)
    # a row of a batched forward does not depend on the other rows, only on
    # the batch shape: each answer must equal, bit for bit, the direct call
    # on its observation at one of the server's batch slots
    equal = np.zeros(SERVE_REQUESTS, bool)
    for slot in cfg.batch_slots:
        padded = np.zeros((-(-SERVE_REQUESTS // slot) * slot,
                           policy.obs_dim), np.float32)
        padded[:SERVE_REQUESTS] = obs
        direct = np.concatenate([
            np.asarray(policy.act_deterministic(padded[j:j + slot]))
            for j in range(0, len(padded), slot)])[:SERVE_REQUESTS]
        equal |= np.all(got == direct, axis=1)
    single = np.stack([np.asarray(policy.act_deterministic(o)) for o in obs])
    hist = dict(sorted(server.stats["batch_hist"].items()))
    _line("serve", ok=bool(equal.all()), requests=SERVE_REQUESTS,
          clients=SERVE_CLIENTS, ticks=server.stats["ticks"],
          batch_hist=json.dumps(hist, separators=(",", ":")),
          slot_compile_s=compile_s, host_wall_s=wall_s,
          equal_to_direct=int(equal.sum()),
          max_abs_gap_vs_batch1=float(np.max(np.abs(got - single))))
    _require(equal.all(), int(equal.sum()))


def phase_mesh4(ctx) -> None:
    import jax
    import numpy as np
    from repro.replay import sharded
    from repro.rl import Experiment

    calls = {"collect_and_add_sharded": 0, "sharded_replay_sample": 0}

    def counted(name):
        inner = getattr(sharded, name)

        def wrapped(*a, **k):
            calls[name] += 1
            return inner(*a, **k)
        return wrapped

    originals = {n: getattr(sharded, n) for n in calls}
    for n in calls:
        setattr(sharded, n, counted(n))
    try:
        spec4 = ctx["spec"].override(**{"execution.mesh_shards": 4})
        print(cuts_line(spec4), flush=True)
        exp4 = Experiment.from_spec(spec4)
        t0 = time.time()
        r4 = exp4.run(STEPS)
        jax.block_until_ready(exp4._ls)
        wall4 = time.time() - t0
    finally:
        for n, f in originals.items():
            setattr(sharded, n, f)
    _require(calls["collect_and_add_sharded"] > 0, calls)
    _require(calls["sharded_replay_sample"] > 0, calls)

    rows = exp4._ls.replay["store"]["data"]["obs"]
    per_dev = {}
    for s in rows.addressable_shards:
        per_dev[s.device.id] = int(np.prod(s.data.shape[:2]))
        print(f"replay obs shard: device={s.device.id} index={s.index} "
              f"shape={tuple(s.data.shape)}", flush=True)
    quarter = spec4.replay.capacity // 4
    _require(len(per_dev) == 4
             and all(v == quarter for v in per_dev.values()), per_dev)

    exp1 = Experiment.from_spec(ctx["spec"])
    t0 = time.time()
    r1 = exp1.run(STEPS)
    jax.block_until_ready(exp1._ls)
    wall1 = time.time() - t0
    _require(np.isfinite(r4.returns).all(), r4.returns)
    _require(_finite_tree(exp4._ls.agent["params"]), "non-finite params")
    gap = abs(float(np.mean(r4.returns)) - float(np.mean(r1.returns)))
    _line("mesh4", ok=gap < 400, shards=4, rows_per_device=quarter,
          sharded_calls=json.dumps(calls, separators=(",", ":")),
          returns_mesh4=[float(r) for r in r4.returns],
          returns_1chip=[float(r) for r in r1.returns],
          mean_return_gap=gap, host_wall_s_mesh4=wall4,
          host_wall_s_1chip=wall1)
    # same env, budget and seed: the curves stay in the same ballpark (the
    # criterion tests/test_train_loop.py applies on fake devices)
    _require(gap < 400, (r4.returns, r1.returns))


PHASES = (phase_train_tpu, phase_parity, phase_fused, phase_serve)


def run_phases(phases, ctx) -> list:
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        try:
            phase(ctx)
        except Exception as e:            # report, run the rest, exit 1
            traceback.print_exc()
            _line(name, ok=False, error=repr(e)[:400].replace("\n", " "))
            failed.append(name)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="four chips: mesh training vs one shard only")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}: run from a checkout",
              2)
    # the parity phase needs the host CPU next to the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _fail(f"JAX found no usable backend: {e}")
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX's default device is {devices[0].platform}; "
              f"this script never falls back to another backend")
    need = 4 if args.mesh4 else 1
    if len(devices) < need:
        _fail(f"needs {need} TPU chips, JAX found {len(devices)}")
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    ctx = {"spec": paper_spec(), "device": devices[0]}
    failed = run_phases((phase_mesh4,) if args.mesh4 else PHASES, ctx)
    if failed:
        _fail(f"failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
