"""Dynamic half of ``repro.check``: runtime sanitizers over a real run.

``python -m repro.check dynamic --preset smoke`` executes three gates the
static rules can only approximate, on an actual (tiny) training run:

* **D001 — transfer guard.** After a warmup pass compiles every chunk the
  schedule needs, the SAME schedule runs again under
  ``jax.transfer_guard("disallow")``: any *implicit* host<->device transfer
  inside the steady-state loop (a stray ``float()``/``np.asarray`` on a
  device value, an un-committed constant) raises. Explicit
  ``jax.device_get`` at the chunk epilogue — the sanctioned barrier — stays
  legal, which is exactly the distinction R004 wants enforced at runtime.
* **D002 — recompile sentinel.** ``Trainer._chunks`` is keyed by the chunk
  signature ``(n_steps, do_eval, do_srank)`` (rl/runner.py), and the scan
  driver's scheduling is deterministic, so the set of compiled programs is
  PREDICTABLE from the spec alone. The sentinel replays the scheduler in
  pure Python (:func:`chunk_signatures`) and fails if the live cache
  diverges — a recompile per chunk (the PR-7 trip-count-1 re-fusion bug)
  or a signature the schedule cannot produce both trip it. The guarded
  second pass must add ZERO new entries.
* **D003 — checkify.** One superstep re-traced under
  ``jax.experimental.checkify`` with NaN + out-of-bounds checks
  (``nan_checks | index_checks``; float-division checks are omitted — the
  masked-softmax/-inf idiom is a false positive there). Device-backend
  replay keeps the superstep pure, so checkify needs no callback plumbing.

Findings reuse the static report format; exit 0 clean, 1 findings.
"""
from __future__ import annotations

import argparse
import sys
import traceback
from typing import List, Optional, Sequence, Set, Tuple

from repro.check.report import Finding, render

Sig = Tuple[int, bool, bool]


def chunk_signatures(start: int, end: int, eval_every: int,
                     srank_every: int) -> List[Sig]:
    """The chunk signatures ``Experiment.run`` dispatches for a step range.

    This mirrors the scheduler in ``rl/experiment.py`` line for line:
    chunks stop at every eval point, every srank point, and ``end``; the
    signature is ``(n_steps, do_eval, do_srank)``. Keep the two in sync —
    tests/test_check.py pins this against the live cache.
    """
    sigs: List[Sig] = []
    step = start
    while step < end:
        stops = [(step // eval_every + 1) * eval_every, end]
        if srank_every:
            stops.append((step // srank_every + 1) * srank_every)
        stop = min(stops)
        do_eval = stop % eval_every == 0
        do_srank = bool(srank_every) and stop % srank_every == 0
        sigs.append((stop - step, do_eval, do_srank))
        step = stop
    return sigs


def _dyn(rule: str, message: str, hint: str) -> Finding:
    return Finding(rule=rule, file="<dynamic>", line=1, message=message,
                   hint=hint)


def run_sanitizers(preset: str = "smoke", *,
                   steps: Optional[int] = None) -> List[Finding]:
    """Run the D001/D002/D003 gates on ``preset``; return findings."""
    import jax
    from jax.experimental import checkify

    from repro.rl import presets
    from repro.rl.experiment import Experiment

    spec = presets.get(preset).override(
        loop="scan", replay_backend="device",
        # srank on: its epilogue fetch is part of the guarded surface
        srank_every=presets.get(preset).eval.every,
        **{"obs.enabled": False, "guard.enabled": False})
    x, ev = spec.execution, spec.eval
    budget = steps or x.total_steps
    findings: List[Finding] = []

    exp = Experiment.from_spec(spec)

    # ---- warmup: compile every program the schedule needs --------------
    exp.run(budget)
    predicted: Set[Sig] = set(chunk_signatures(0, budget, ev.every,
                                               ev.srank_every))
    compiled = set(exp.trainer._chunks)
    if compiled != predicted:
        findings.append(_dyn(
            "D002",
            f"compile cache after warmup holds {sorted(compiled)}, "
            f"scheduler predicts {sorted(predicted)}",
            "a signature outside the prediction means the chunk key space "
            "grew (check Trainer.chunk_fn's sig tuple) or the scheduler "
            "in Experiment.run diverged from check.dynamic"
            ".chunk_signatures"))

    # ---- guarded steady state: same schedule, zero implicit transfers --
    # and zero new compilations (the second run re-chunks the SAME
    # signatures from a different absolute step)
    n_before = len(exp.trainer._chunks)
    try:
        with jax.transfer_guard("disallow"):
            exp.run(budget)
    except Exception:  # jax raises backend-specific transfer errors
        tb = traceback.format_exc(limit=20)
        findings.append(_dyn(
            "D001",
            "implicit host<->device transfer inside the guarded "
            f"steady-state run:\n{tb.strip()}",
            "fetch device values only at the chunk epilogue with explicit "
            "jax.device_get; never float()/int()/np.asarray a jnp value "
            "mid-loop"))
    n_new = len(exp.trainer._chunks) - n_before
    if n_new:
        findings.append(_dyn(
            "D002",
            f"{n_new} chunk program(s) recompiled during the guarded "
            f"steady-state pass (cache keys now "
            f"{sorted(exp.trainer._chunks)})",
            "the second pass re-chunks the same signatures, so any new "
            "cache entry is a schedule-dependent recompile — the "
            "PR-7 trip-count-1 bug class"))

    # ---- checkify one superstep ----------------------------------------
    try:
        errs = checkify.nan_checks | checkify.index_checks
        step1 = lambda s: exp.trainer._superstep(s)[0]  # noqa: E731
        err, _ = jax.jit(checkify.checkify(step1, errors=errs))(exp._ls)
        err.throw()
    except Exception as e:
        findings.append(_dyn(
            "D003",
            f"checkify flagged one superstep: {e}",
            "a NaN or out-of-bounds index inside the superstep is a "
            "training-pathology bug (the class the srank/guard machinery "
            "watches for) — bisect with checkify on the python driver"))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.check dynamic",
        description="transfer-guard / recompile / checkify sanitizer run")
    ap.add_argument("--preset", default="smoke",
                    help="preset to run (default: smoke)")
    ap.add_argument("--steps", type=int, default=None,
                    help="override the per-phase step budget")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    findings = run_sanitizers(args.preset, steps=args.steps)
    print(render(findings))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
