"""Training traffic: the scanned superstep driven through ``Experiment.run``
in whole chunks of ``chunk_steps`` updates for the measured window.

Set-up builds the one ``Experiment`` the window uses. Its state (seeded
weights, actor pool, replay warmed by the random-policy collect) is made
on the device in one jitted call. From that state set-up runs the
window's two chunk programs, (``chunk_steps``, no eval) and
(``chunk_steps``, eval), and the window goes on from where they end.

What the window's programs produce is checked in two links. The
Trainer's own 1-update chunk program takes the same seeded state through
``chunk_steps`` updates: its first three are read out for the reference
(a ``chunk_steps`` scan keeps its inner states to itself), and its
parameters after the last are compared with those the window's first
program produced. The window's eval program is compared with its
no-eval program run once more from the same state.

The window runs ``Experiment.run(chunk_steps)`` calls for ``--seconds``
with at most two chunks in flight, and ends on ``block_until_ready`` of
the carried state. Evaluation folds into every chunk that reaches a
multiple of ``eval.every`` updates, as in training.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

import harness

B1 = 0.9     # Adam's first-moment decay (the optimizer state holds 0.1 g)
# losses compared at update 1: both are means of squares, bounded away from
# zero; the actor loss (alpha * logp - min Q) can sit near zero by
# cancellation, so its relative gap is printed but not compared
LOSSES = ("critic_loss", "aux_loss")
FIELDS = ("obs", "act", "rew", "next_obs")


def _leaf_norms(tree) -> Dict[str, float]:
    """L2 norm of each leaf, keyed by its path."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))) for p, x in flat}


def _trained(params):
    out = {"actor": params["actor"], "critics": params["critics"],
           "alpha": params["log_alpha"]}
    if "ofenet" in params:
        out["ofenet"] = params["ofenet"]["online"]
    return out


def _delta_norms(params, params0):
    """Per-leaf norm of each trained leaf's change from ``params0``."""
    import jax
    import jax.numpy as jnp
    return _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        _trained(params), _trained(params0)))


def readings_of(params0, grads1, params3, loss1, batch1, collected,
                prio_total1) -> dict:
    """The readings of the first three updates that ``compare`` takes,
    in one format for the program and the reference."""
    return {"loss": loss1, "grad": _leaf_norms(grads1),
            "delta": _delta_norms(params3, params0), "batch1": batch1,
            "collected": collected, "prio_total": prio_total1}


def setup(ctx) -> dict:
    """Build the Experiment, run the window's chunk programs from the
    seeded state and the 1-update chain beside them, read both. Returns
    the run's state for the window."""
    import jax
    import jax.numpy as jnp
    from repro.rl.experiment import Experiment, ExperimentSpec

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    k = tr["chunk_steps"]
    spec = ExperimentSpec.from_dict(cfg["spec"])
    if spec.eval.every % k:
        raise harness.BenchError(f"chunk_steps={k} must divide "
                                 f"eval.every={spec.eval.every}")
    exp = Experiment.from_spec(spec)
    trainer = exp.trainer
    init_alpha = cfg["hyperparameters"]["init_alpha"]

    @jax.jit
    def build(seed, wkey):
        # Trainer.init's op and PRNG schedule with the seed traced, not
        # baked into the program (as Fleet._member_init does it), so that
        # every seed finds the one compiled program in the cache
        ls, kw = trainer._fresh_state(seed)
        warm = max(trainer.warmup_steps // trainer.n_actors, 1,
                   trainer.n_step)
        actors, nstate, rstate = trainer._op_collect_add(
            trainer._rand_policy, ls.agent["params"], ls.actors, ls.nstep,
            ls.replay, kw, ls.step, steps=warm, drop=trainer.n_step - 1)
        params = harness.make_params(
            jax.eval_shape(lambda: ls.agent["params"]), init_alpha, wkey)
        return ls._replace(agent=dict(ls.agent, params=params),
                           actors=actors, nstep=nstate, replay=rstate)

    t = time.perf_counter()
    ls0 = build(jnp.int32(harness.seed31(seed)), harness.weights_key(seed))
    jax.block_until_ready(ls0)
    harness.log(f"set-up: seeded state {time.perf_counter() - t:.1f}s")

    # the window's programs, from the seeded state
    t = time.perf_counter()
    exp._ls = ls0
    exp.run(k)
    win1 = exp._ls
    exp.run(k, eval_at_end=True)
    win2 = exp._ls
    noeval2, _ = trainer.chunk_fn(k, False)(win1)
    jax.block_until_ready((win2, noeval2))
    harness.log(f"set-up: window programs {time.perf_counter() - t:.1f}s")

    # the same k updates through the 1-update chunk program
    t = time.perf_counter()
    one = trainer.chunk_fn(1, False)
    s = ls0
    for i in range(1, k + 1):
        s, out = one(s)
        if i == 1:
            s1, scal1, batch1 = s, out["scal"], out["last"][0]
        elif i == 3:
            s3 = s
    jax.block_until_ready(s)
    harness.log(f"set-up: {k} 1-update chunks "
                f"{time.perf_counter() - t:.1f}s")

    n = trainer.n_actors
    warm_rows = max(spec.execution.warmup_steps // n, 1) * n

    @jax.jit
    def read(ls0, s1, s3, scal1, batch1, chain, w1, w2, noeval2):
        p0 = ls0.agent["params"]
        grads = {name: jax.tree_util.tree_map(lambda m: m / (1 - B1),
                                              s1.agent["opt"][name]["mu"])
                 for name in _trained(p0)}
        data = s1.replay["store"]["data"]
        r = readings_of(
            p0, grads, s3.agent["params"],
            {m: scal1[m] for m in LOSSES + ("actor_loss",) if m in scal1},
            batch1, {f: data[f][warm_rows:warm_rows + n] for f in FIELDS},
            s1.replay["tree"][1])
        r["chunks"] = {
            name: {"window": _delta_norms(w.agent["params"], p0),
                   "chain": _delta_norms(c.agent["params"], p0)}
            for name, w, c in (("chunk_gap", w1, chain),
                               ("chunk_gap.eval", w2, noeval2))}
        return r

    readings = jax.device_get(read(ls0, s1, s3, scal1, batch1, s, win1,
                                   win2, noeval2))
    del ls0, s, s1, s3, out, batch1, scal1, win1, noeval2
    jax.block_until_ready(exp._ls)
    return {"exp": exp, "readings": readings, "k": k}


def window(ctx, st: dict, seconds: float, tracer) -> dict:
    """Whole chunks for ``seconds``; the traced stretch, when on, is
    ``traffic.trace_chunks`` chunks at the start of the window."""
    import jax
    exp, k = st["exp"], st["k"]
    trace_chunks = ctx["traffic"]["trace_chunks"]
    c0 = ctx["compiles"].n
    chunks, prev = 0, exp._ls
    traced_updates = 0
    t0 = time.perf_counter()
    if tracer.on:
        with tracer:
            for _ in range(trace_chunks):
                exp.run(k)
                chunks += 1
            jax.block_until_ready(exp._ls)
        traced_updates = trace_chunks * k
        prev = exp._ls
    while True:
        exp.run(k)
        chunks += 1
        cur = exp._ls
        jax.block_until_ready(prev)
        prev = cur
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(exp._ls)
    dt = time.perf_counter() - t0
    compiles = ctx["compiles"].n - c0
    params_ok = bool(jax.device_get(jax.tree_util.tree_reduce(
        lambda a, b: a & b, jax.tree_util.tree_map(
            lambda x: jax.numpy.all(jax.numpy.isfinite(x)),
            exp._ls.agent["params"]))))
    return {"updates": chunks * k, "seconds": dt, "finite": params_ok,
            "traced_updates": traced_updates, "evals": len(exp.returns),
            "compiles": compiles}


def _norm_gap(prog: Dict[str, float], ref: Dict[str, float],
              gref: Dict[str, float]) -> float:
    """Worst leaf's |norm_prog - norm_ref| over max(norm_ref, median leaf
    norm), leaving out leaves whose reference step-1 gradient is under a
    thousandth of the median leaf's. Where the reference side has not
    moved at all, any gap is infinite."""
    gmed = float(np.median(list(gref.values())))
    rmed = float(np.median(list(ref.values())))
    worst = 0.0
    for leaf, r in ref.items():
        if gref[leaf] < 1e-3 * gmed:
            continue
        gap = abs(prog[leaf] - r)
        den = max(r, rmed)
        worst = max(worst, gap / den if den > 0 else
                    (np.inf if gap > 0 else 0.0))
    return worst


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers that decide ``correct`` (see PERF.md for each limit):
    the program's readings (``setup``) against the reference's
    (``reference``)."""
    f = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
    gref = f(ref["grad"])
    lp, lr = prog["loss"], ref["loss"]
    out = {"loss_gap.s1": max(abs(float(lp[m]) - float(lr[m]))
                              / abs(float(lr[m])) for m in LOSSES if m in lp),
           "actor_loss_gap.s1": abs(float(lp["actor_loss"])
                                    - float(lr["actor_loss"]))
           / abs(float(lr["actor_loss"]))}
    out["grad_gap"] = _norm_gap(f(prog["grad"]), gref, gref)
    out["delta_gap"] = _norm_gap(f(prog["delta"]), f(ref["delta"]), gref)
    b1, rb = prog["batch1"], ref["batch1"]
    off = np.zeros(rb["obs"].shape[0], bool)
    for fld in FIELDS + ("done", "weight"):
        a = np.asarray(b1[fld], np.float64).reshape(off.shape[0], -1)
        r = np.asarray(rb[fld], np.float64).reshape(off.shape[0], -1)
        off |= np.any(np.abs(a - r) > 1e-2 * (1 + np.abs(r)), axis=1)
    out["batch_rows_off"] = float(off.mean())
    col = prog["collected"]
    out["collect_gap"] = max(float(np.max(np.abs(
        np.asarray(col[fld], np.float64)
        - np.asarray(ref["collected"][fld], np.float64)))) for fld in col)
    out["prio_total_gap"] = abs(float(prog["prio_total"])
                                - float(ref["prio_total"])) \
        / float(ref["prio_total"])
    for name, c in prog.get("chunks", {}).items():
        out[name] = _norm_gap(f(c["window"]), f(c["chain"]), gref)
    return out


def reference(ctx, dtype_name: str = "float32") -> dict:
    """The reference's readings of its first three updates from the seed
    (weights made again from the seed, on the device, after the program
    is freed)."""
    import jax
    import jax.numpy as jnp
    from reference import sac_ref
    cfg, seed = ctx["config"], ctx["seed"]
    template = harness.params_template(cfg)
    params0 = jax.jit(lambda k: harness.make_params(
        template, cfg["hyperparameters"]["init_alpha"], k))(
            harness.weights_key(seed))
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    prec = "highest" if dtype_name == "float32" else "default"
    with jax.default_matmul_precision(prec):
        steps = sac_ref.follow(cfg, params0, harness.seed31(seed), 3, dtype)
    return jax.device_get(readings_of(
        params0, steps[0]["grads"], steps[2]["params"], steps[0]["metrics"],
        steps[0]["batch"], steps[0]["collected"], steps[0]["prio_total"]))


def run(ctx) -> dict:
    t0 = time.perf_counter()
    st = setup(ctx)
    setup_s = time.perf_counter() - t0
    harness.log(f"set-up {setup_s:.1f}s")
    tracer = harness.TraceWindow(ctx["trace"], ctx["workload"])
    w = window(ctx, st, ctx["seconds"], tracer)
    harness.log(f"window: {w['updates']} updates in {w['seconds']:.3f}s, "
                f"{w['evals']} evals, {w['compiles']} compiles inside the "
                f"window")
    device = harness.device_info(ctx["devices"])
    readings = st["readings"]
    del st
    gc.collect()
    red = tracer.reduce()
    numbers = compare(readings, reference(ctx))
    ctx["run"] = {"updates_per_s": w["updates"] / w["seconds"], **w}
    return {"setup_s": setup_s, "device": device,
            "trace": red, "numbers": numbers,
            "attempted": w["updates"],
            "failed": 0 if w["finite"] else w["updates"],
            "end_to_end": {"updates_per_s": w["updates"] / w["seconds"]}}
