"""Vmapped experiment fleets: a whole paper figure as ONE device program.

The paper's results are all sweeps — depth grids (fig1), width grids
(fig3/fig4), seed batteries — historically run as a sequential loop of
independent ``Experiment``s, paying N x M full dispatch/compile/loop costs.
Every env in ``rl/envs.py`` is pure JAX and the scan superstep is a pure
function of ``TrainLoopState``, so entire training runs batch with
``jax.vmap``: a ``Fleet`` stacks its members' ``TrainLoopState``s along a
leading MEMBER axis and advances all of them through one jitted chunk
program whose loop body is ``jax.vmap(Trainer._superstep)``.

    from repro.rl import Sweep

    sweep = Sweep.from_grid("fig3-width",
                            axis={"num_units": [64, 256]}, seeds=5)
    sweep.run()                       # 2 compiled programs, 10 members
    for m in sweep.results():
        print(m.label, m.result.max_return)

Semantics
---------
* **One compile per sub-fleet.** Members of a ``Fleet`` must share one
  compiled computation — i.e. be identical specs modulo
  ``execution.seed`` (seeds are data: fleet init vmaps
  ``jax.random.key(seed)`` over the member seed vector). Any other spec
  difference (width, depth, activation, ...) changes the program, so
  ``Sweep.from_grid`` PARTITIONS the grid into per-point sub-fleets and
  reports the partition (``Sweep.partition``); building a ``Fleet`` from
  heterogeneous specs directly raises ``SpecError``.
* **XLA sum-tree, no mesh.** ``replay.kernel="pallas"`` (vmap-of-pallas
  is unpinned, see ROADMAP) and ``execution.mesh_shards`` (member-axis and
  mesh-axis composition is future work) are rejected with ``SpecError``.
* **Scheduling exactly as today.** Eval/srank fire at absolute multiples of
  ``eval.every`` / ``eval.srank_every`` — the fleet chunk loop mirrors
  ``Experiment.run``'s stop computation, so member k of a fleet evaluates
  at the same absolute steps as a solo ``Experiment`` with the same spec.
* **Early-stop masking.** A per-member ``done`` mask rides the chunk as a
  TRACED argument (no recompile when it changes): every member computes
  through the whole segment — the scan body stays the bare vmapped
  superstep with in-place replay writes — and ONE leaf-wise select at
  segment end restores a done member's carry (params, replay, PRNG key,
  step) from the segment input, discarding its throwaway trajectory.
  ``vmap`` computes members independently, so that trajectory can't touch
  a neighbor, and because there is a single compiled program, freezing
  changes values, never code: neighbors are bitwise unaffected. Frozen
  members cost device FLOPs (the program stays uniform) but no extra host
  round-trips, their histories stop accumulating, and unfreezing resumes
  them bit-exactly where they stopped. ``run(stop_at_return=...)`` sets
  the mask automatically; ``set_done`` sets it by hand.
* **Checkpointing through ``ckpt.py`` unchanged.** The member axis is just
  another leading leaf dimension: ``save`` writes the stacked state (typed
  PRNG keys as raw key data) plus per-member histories/labels/done in the
  metadata; ``restore`` rebuilds the restore template abstractly via
  ``jax.eval_shape`` over the vmapped init (no throwaway warmup program)
  and resumes bitwise: the fleet compiles ONE chunk program whose segment
  length and eval/srank flags are runtime values (a ``fori_loop`` with a
  traced bound — the solo driver's uniform-scan-body guarantee from PR 5,
  taken to its limit because vmapped bodies round differently once XLA
  unrolls a static trip-count-1 loop), so fleet ``run(N); save; restore;
  run(M)`` == ``run(N+M)`` at ANY split point by construction.
* **Per-member obs demux.** Each member gets its OWN ``ObsRun``: the fleet
  chunk stream comes back with a member axis and is sliced per member on
  the host, file sinks write into ``<log_dir>/<member-slug>/`` subdirs,
  and every row is tagged ``"member"`` (``repro.obs.report`` accepts the
  sweep directory and merges member streams).

Member-vs-solo parity: a fleet member and a solo ``Experiment`` with the
same spec+seed run the same ops in the same
PRNG schedule, but vmap batches the member's matmuls with its neighbors',
and batched reductions may reassociate floats — so parity is ALLCLOSE, not
bitwise: eval returns and final params agree within ``SOLO_PARITY_RTOL`` /
``SOLO_PARITY_ATOL`` (tests/test_sweep.py pins this). Fleet resume parity
(fleet vs the same fleet interrupted) IS bitwise.

PBT stretch: ``exploit_explore()`` runs truncation selection on the member
axis between chunks — bottom-``fraction`` members copy the agent state
(params/opt/step) of top members and optionally perturb their copied
params with per-member-key noise; actors/replay/step stay the member's
own. Naturally this forfeits solo parity for overwritten members.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import re
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core.effective_rank import effective_rank
from repro.guard.monitor import GuardViolation, Monitor
from repro.guard.store import DurableStore
from repro.obs.stream import ObsRun
from repro.obs.trace import annotate
from repro.rl.envs import eval_returns
from repro.rl.experiment import (ExperimentSpec, SpecError, _is_key,
                                 _rekey, _unkey)
from repro.rl.runner import RunResult, Trainer, TrainLoopState

# Documented member-vs-solo tolerance (see module docstring): the member's
# computation is batched with its fleet neighbors', so float reassociation
# in batched matmuls/reductions shifts trajectories by rounding error that
# training then amplifies over a chunk. Measured at smoke scale (12 steps,
# pendulum SAC, CPU): eval returns agree to ~1e-5 relative (abs diff
# <= 8e-3 on returns of magnitude ~1e3), final params to ~2e-7 relative.
# These bounds leave ~50x headroom over the measurement.
SOLO_PARITY_RTOL = 5e-4
SOLO_PARITY_ATOL = 1e-4

_CKPT_KEY = "fleet"


def _slug(label: str) -> str:
    """Member label -> filesystem-safe obs subdir name."""
    return re.sub(r"[^A-Za-z0-9_.,=-]+", "-", label).strip("-") or "member"


def _fleet_signature(spec: ExperimentSpec) -> dict:
    """The compiled-program identity of a spec: everything except the seed
    (seeds are data — the only spec axis a single fleet can batch over)."""
    d = spec.to_dict()
    d["execution"]["seed"] = 0
    return d


def _diff_paths(a, b, prefix="") -> List[str]:
    """Dotted paths where two signature dicts disagree (error reporting)."""
    out: List[str] = []
    for k in sorted(set(a) | set(b)):
        pa, pb = a.get(k), b.get(k)
        path = f"{prefix}{k}"
        if isinstance(pa, dict) and isinstance(pb, dict):
            out += _diff_paths(pa, pb, path + ".")
        elif pa != pb:
            out.append(f"{path} ({pa!r} vs {pb!r})")
    return out


def _tree_where(mask_1d, on_true, on_false):
    """Leaf-wise ``where`` over two matching pytrees whose leaves carry a
    leading member axis; ``mask_1d`` is ``(M,)`` bool, broadcast to each
    leaf's rank. Works on typed PRNG key leaves (jnp.where supports them,
    same pattern as the env auto-reset in ``apex.collect``)."""
    def sel(t, f):
        m = mask_1d.reshape(mask_1d.shape + (1,) * (jnp.ndim(t) - 1))
        return jnp.where(m, t, f)
    return jax.tree_util.tree_map(sel, on_true, on_false)


def _unkey_abstract(tree):
    """`_unkey` for ShapeDtypeStruct trees: typed-key SDS leaves become the
    raw key-data SDS (what the checkpoint actually stores)."""
    return jax.tree_util.tree_map(
        lambda s: jax.eval_shape(jax.random.key_data, s) if _is_key(s)
        else s, tree)


# ------------------------------------------------------------------ fleet

class Fleet:
    """N training runs of ONE compiled shape, advanced in lockstep.

    All member specs must be identical modulo ``execution.seed`` (use
    ``Sweep.from_grid`` to partition a heterogeneous grid into fleets).
    The public surface mirrors ``Experiment``: ``run`` / ``save`` /
    ``restore`` / ``results``, plus the fleet-only ``set_done`` and
    ``exploit_explore``.
    """

    def __init__(self, specs: Sequence[ExperimentSpec],
                 labels: Optional[Sequence[str]] = None,
                 points: Optional[Sequence[dict]] = None):
        specs = list(specs)
        if not specs:
            raise SpecError("Fleet needs at least one member spec")
        base = specs[0]
        if base.replay.kernel != "xla":
            raise SpecError(
                "fleets require replay.kernel='xla': vmap-of-pallas_call "
                "for the sum-tree kernel is unpinned (ROADMAP kernel "
                "scale-up item); the jnp reference path batches cleanly.")
        if base.execution.mesh_shards:
            raise SpecError(
                "fleets do not compose with execution.mesh_shards yet: "
                "the member axis and the mesh 'data' axis would both claim "
                "the leading dimension. Run mesh-sharded specs solo.")
        if base.guard.enabled and base.guard.policy == "skip":
            raise SpecError(
                "fleets support guard.policy 'halt' or 'rollback', not "
                "'skip': the skip policy rewinds the pre-segment snapshot, "
                "which in a fleet would rewind EVERY member (state is one "
                "stacked tree) — per-member rollback through the durable "
                "store keeps healthy neighbors bitwise untouched instead.")
        sig0 = _fleet_signature(base)
        for i, s in enumerate(specs[1:], 1):
            diff = _diff_paths(sig0, _fleet_signature(s))
            if diff:
                raise SpecError(
                    f"fleet member {i} differs from member 0 beyond the "
                    f"seed: {', '.join(diff)}. One fleet is ONE compiled "
                    f"program, so members may only differ in "
                    f"execution.seed; specs that change shapes or compute "
                    f"(width, depth, activation, ...) need their own "
                    f"sub-fleet — Sweep.from_grid partitions a grid this "
                    f"way automatically.")
        self.specs = specs
        self.spec = base
        self.n_members = len(specs)
        self.seeds = np.asarray([s.execution.seed for s in specs], np.int32)
        if labels is None:
            labels = [f"seed={s}" for s in self.seeds]
        if len(labels) != len(specs):
            raise SpecError(f"{len(labels)} labels for {len(specs)} members")
        self.labels = [str(l) for l in labels]
        self.points = [dict(p) for p in points] if points is not None \
            else [{} for _ in specs]
        self.trainer = Trainer(base)
        self._chunks: Dict[tuple, Callable] = {}
        self._fls = None                      # stacked TrainLoopState
        self.step = 0
        self.done = np.zeros(self.n_members, bool)
        self.returns: List[List[float]] = [[] for _ in specs]
        self.eval_steps: List[List[int]] = [[] for _ in specs]
        self.sranks: List[List[int]] = [[] for _ in specs]
        self._rows: List[List[Dict[str, float]]] = [[] for _ in specs]
        self._last_metrics: List[Dict[str, float]] = [{} for _ in specs]
        self._wall = 0.0
        self._obs = [self._member_obs(label) for label in self.labels]
        # fleet guard: one Monitor per member for detection state (spike
        # windows are per-member), one fleet-level Monitor holding the
        # shared recovery budget
        g = base.guard
        self._guard = Monitor(g) if g.enabled else None
        self._guard_members = [Monitor(g) for _ in specs] if g.enabled \
            else []
        self._guard_store = None       # DurableStore via attach_guard()

    def _member_obs(self, label: str) -> ObsRun:
        """One ObsRun per member: file sinks write under a per-member
        subdir of the base log_dir, every row is tagged with the label."""
        ospec = self.spec.obs
        if ospec.enabled and ospec.log_dir:
            ospec = self.spec.override(**{"obs.log_dir": str(
                Path(ospec.log_dir) / _slug(label))}).obs
        return ObsRun(ospec, member=label)

    # --------------------------------------------------------- fleet state
    def _member_init(self, seed):
        """Solo init + warmup for one member (same op/PRNG schedule as
        ``Trainer.init``) — vmapped over the member
        seed vector so the whole fleet initializes as one program."""
        tr = self.trainer
        ls, kw = tr._fresh_state(seed)
        warm = max(tr.warmup_steps // tr.n_actors, 1, tr.n_step)
        actors, nstate, rstate = tr._op_collect_add(
            tr._rand_policy, ls.agent["params"], ls.actors, ls.nstep,
            ls.replay, kw, ls.step, steps=warm, drop=tr.n_step - 1)
        return ls._replace(actors=actors, nstep=nstate, replay=rstate)

    def _ensure_init(self):
        if self._fls is None:
            init_j = self.trainer._count(jax.jit(jax.vmap(self._member_init)))
            self._fls = init_j(jnp.asarray(self.seeds))

    def _state_template(self):
        """Abstract (ShapeDtypeStruct) stacked TrainLoopState — the restore
        template, built without executing any init program."""
        return jax.eval_shape(
            jax.vmap(self._member_init),
            jax.ShapeDtypeStruct((self.n_members,), jnp.int32))

    # -------------------------------------------------------- the chunk
    @property
    def _seg_cap(self) -> int:
        """Static stream-buffer capacity: the longest segment ``run()`` can
        schedule. Boundaries fall on every multiple of each active cadence,
        so consecutive boundaries are at most the smallest cadence apart."""
        ev = self.spec.eval
        cads = [c for c in (ev.every, ev.srank_every) if c]
        return min(cads) if cads else self.spec.execution.total_steps

    def chunk_fn(self, n_steps: int, do_eval: bool,
                 do_srank: bool = False) -> Callable:
        """A segment of ``n_steps`` vmapped supersteps (+ optional
        per-member eval/srank) over ``(stacked_state, done_mask)``.

        Every segment executes ONE uniform jitted program: the segment
        length is a traced ``fori_loop`` bound and eval/srank are traced
        ``lax.cond`` predicates, so ``(n_steps, do_eval, do_srank)`` are
        runtime VALUES, never compile-time constants. That is what makes
        fleet resume bitwise at ANY split: re-chunking the same step
        sequence cannot change the program, because there is only one.
        (The solo driver's per-length ``lax.scan`` chunks are bitwise too,
        but under vmap they were NOT — XLA unrolls a trip-count-1 loop and
        refuses the batched body's loop-form fusions, shifting rounding by
        ~1e-10 per step; ``optimization_barrier`` around the body does not
        stop it. A dynamic bound removes the unroll by construction.)

        Early-stop masking is applied ONCE per segment, not per step: the
        loop body is the bare vmapped superstep (so replay writes stay
        in-place — a per-step ``where`` on the carry would keep the old
        buffers alive and force a full-replay memcpy per member per step),
        every member computes through the whole segment, and a single
        leaf-wise select at the end restores a done member's carry —
        params, replay, actors AND key — from the segment input. ``vmap``
        guarantees members are computed independently, so a frozen
        member's discarded throwaway trajectory cannot touch a neighbor,
        and since the mask is traced too, freezing changes values, never
        code — bitwise invisible to neighbors. The host discards a done
        member's segment outputs (``Fleet._record`` skips them)."""
        do_srank = do_srank and bool(self.trainer.srank_every)
        fn = self._uniform_fn()

        def call(fls: TrainLoopState, done):
            return fn(fls, done, jnp.int32(n_steps), jnp.bool_(do_eval),
                      jnp.bool_(do_srank))
        return call

    def _uniform_fn(self) -> Callable:
        """THE fleet chunk program (compiled once per fleet)."""
        if "uniform" not in self._chunks:
            def chunk(fls: TrainLoopState, done, n, de, ds):
                return self._chunk_body(fls, done, n, de, ds)
            self._chunks["uniform"] = self.trainer._count(jax.jit(chunk))
        return self._chunks["uniform"]

    def _chunk_body(self, fls: TrainLoopState, done, n, de, ds):
        """Traced segment body shared by the uniform chunk program and
        ``fused_fn``; ``n`` / ``de`` / ``ds`` are traced scalars. Output
        shapes are schedule-independent: the obs stream fills the first
        ``n`` rows of a ``(_seg_cap, M)`` buffer, and eval/srank slots are
        zeros on segments that skip them (the host epilogue knows the
        schedule and never reads those)."""
        tr = self.trainer
        fls_in = fls
        vstep = jax.vmap(tr._superstep)
        _, m_t, b_t = jax.eval_shape(vstep, fls)
        zeros = lambda t: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), t)
        # per-member scalars carry the member axis -> ndim == 1
        stream_keys = tuple(sorted(
            k for k, v in m_t.items() if v.ndim == 1)) \
            if tr.obs_stream else ()
        cap = self._seg_cap
        buf0 = {k: jnp.zeros((cap,) + m_t[k].shape, m_t[k].dtype)
                for k in stream_keys}

        def body(i, carry):
            (c, m, b), buf = carry
            nc, nm, nb = vstep(c)
            buf = {k: jax.lax.dynamic_update_index_in_dim(
                buf[k], nm[k], i, 0) for k in buf}
            return (nc, nm, nb), buf

        (fls, metrics, batch), buf = jax.lax.fori_loop(
            0, n, body, ((fls, zeros(m_t), zeros(b_t)), buf0))
        out = {"scal": {k: v for k, v in metrics.items()
                        if getattr(v, "ndim", None) == 1}}
        if stream_keys:
            out["stream"] = buf                  # (cap, M) per scalar
        if bool(tr.srank_every):
            with jax.named_scope("repro.fleet_srank"):
                qf = metrics["q_features"]
                sr_t = jax.eval_shape(jax.vmap(effective_rank), qf)
                out["srank"] = jax.lax.cond(
                    ds, lambda q: jax.vmap(effective_rank)(q),
                    lambda q: jnp.zeros(sr_t.shape, sr_t.dtype), qf)

        def ev_true(f):
            def member_eval(ls_m):
                key, ke = jax.random.split(ls_m.key)
                rets = eval_returns(
                    tr.env, tr.policy0.with_params(ls_m.agent["params"]),
                    ke, tr.eval_episodes)
                return key, rets
            return jax.vmap(member_eval)(f)

        with jax.named_scope("repro.fleet_eval"):
            r_t = jax.eval_shape(ev_true, fls)[1]
            keys, rets = jax.lax.cond(
                de, ev_true,
                lambda f: (f.key, jnp.zeros(r_t.shape, r_t.dtype)), fls)
            fls, out["eval"] = fls._replace(key=keys), rets
        # segment-end freeze: restore done members' carries (incl. the
        # PRNG key, so a frozen member consumes no splits and unfreezing
        # resumes bit-exactly) from the segment input; their throwaway
        # outputs above are skipped by the host epilogue
        fls = _tree_where(done, fls_in, fls)
        return fls, out

    def fused_fn(self, n_segs: int) -> Callable:
        """A whole ``run()``'s segment schedule as ONE jitted program.

        The schedule is DATA, not code: ``lax.scan`` over per-segment
        ``(n_steps, do_eval, do_srank)`` arrays, each step running the same
        uniform segment body as ``chunk_fn`` (lengths/flags stay traced
        scalars inside the scan, so nothing constant-folds back into the
        program). One dispatch runs the whole paper-figure pass, evals
        included; outputs come back stacked on a leading segment axis for
        the host epilogue to unstack. Compiled once per segment COUNT —
        any schedule of the same length reuses the program."""
        sig = ("fused", n_segs)
        if sig in self._chunks:
            return self._chunks[sig]

        def fused(fls: TrainLoopState, done, ns, des, dss):
            def seg(c, x):
                return self._chunk_body(c, done, *x)
            return jax.lax.scan(seg, fls, (ns, des, dss))

        self._chunks[sig] = self.trainer._count(jax.jit(fused))
        return self._chunks[sig]

    # ------------------------------------------------------------ running
    def run(self, steps: Optional[int] = None, *,
            stop_at_return: Optional[float] = None,
            progress: Optional[Callable] = None,
            eval_at_end: bool = False) -> List[RunResult]:
        """Advance every non-done member ``steps`` gradient steps (default:
        the spec budget), evaluating at absolute multiples of
        ``eval.every`` exactly like ``Experiment.run``'s scan driver.

        ``stop_at_return`` freezes a member (sets its done mask) once its
        latest eval return reaches the threshold; frozen members keep their
        state/history and stop consuming PRNG splits. ``progress`` is
        called per recorded eval as ``progress(label, step, ret)``.

        Without ``stop_at_return`` the whole segment schedule is dispatched
        as ONE jitted program (``fused_fn``) — a uniform eval cadence runs
        the full pass, evals included, in a single device call. With it,
        the done mask must react to each eval on the host, so the run
        falls back to one dispatch per segment. Both paths execute the
        same segment bodies in the same order. Returns ``results()``."""
        # host-side driver timing only: every time.time() below runs
        # between device dispatches, never inside a traced scope, so wall
        # clocks cannot leak into a compiled program (R001's failure mode)
        t0 = time.time()
        ev = self.spec.eval
        eval_every, srank_every = ev.every, ev.srank_every
        if steps is None:
            steps = self.spec.execution.total_steps
        self._ensure_init()
        step, end = self.step, self.step + steps
        segs = []                         # (n, do_eval, do_srank, s0, stop)
        s = step
        while s < end:
            stops = [(s // eval_every + 1) * eval_every, end]
            if srank_every:
                stops.append((s // srank_every + 1) * srank_every)
            stop = min(stops)
            do_eval = (stop % eval_every == 0
                       or (eval_at_end and stop == end))
            do_srank = (bool(srank_every) and stop % srank_every == 0
                        and bool(self.trainer.srank_every))
            segs.append((stop - s, do_eval, do_srank, s, stop))
            s = stop
        # the guard must inspect every segment's outputs on the host before
        # the next one runs (a rollback swaps member state between
        # segments), so a guarded fleet always takes the per-segment path
        if stop_at_return is None and self._guard is None and segs:
            fn = self.fused_fn(len(segs))
            ns = jnp.asarray([g[0] for g in segs], jnp.int32)
            des = jnp.asarray([g[1] for g in segs], bool)
            dss = jnp.asarray([g[2] for g in segs], bool)
            tc = time.time()
            with annotate("repro.fleet_fused_dispatch"):
                self._fls, outs = fn(self._fls, jnp.asarray(self.done),
                                     ns, des, dss)
                outs = jax.device_get(outs)   # one host fetch for the pass
            wall_c = (time.time() - tc) / len(segs)
            for j, (n, de, ds, s0, stop) in enumerate(segs):
                oj = jax.tree_util.tree_map(lambda v: v[j], outs)
                self._record(oj, s0, stop, de, ds, wall_c, None, progress)
        else:
            for n, de, ds, s0, stop in segs:
                tc = time.time()
                with annotate("repro.fleet_chunk_dispatch"):
                    self._fls, out = self.chunk_fn(n, de, ds)(
                        self._fls, jnp.asarray(self.done))
                bad: frozenset = frozenset()
                if self._guard is not None:
                    viol = self._guard_check(s0, stop, ds, out)
                    if viol:
                        bad = self._guard_recover_members(viol, stop)
                self._record(out, s0, stop, de, ds, time.time() - tc,
                             stop_at_return, progress, skip=bad)
        self.step = end
        self._wall += time.time() - t0
        for obs in self._obs:
            if obs.enabled:
                obs.drain()
        return self.results()

    def _record(self, out, s0: int, stop: int, do_eval: bool,
                do_srank: bool, wall_c: float, stop_at_return, progress,
                skip: frozenset = frozenset()):
        """Host epilogue for one segment's outputs: stream flush, srank /
        eval bookkeeping per active member, early-stop mask updates.
        ``skip`` members (just rolled back by the guard) have their
        segment outputs discarded — they are divergence garbage."""
        if "stream" in out:
            # (cap, M) buffers; only the first stop-s0 rows were written
            stream = {k: np.asarray(v)[:stop - s0]
                      for k, v in jax.device_get(out["stream"]).items()}
            for m, obs in enumerate(self._obs):
                if self.done[m] or m in skip or not obs.enabled:
                    continue
                obs.flush_chunk(s0, {k: v[:, m] for k, v in stream.items()})
                obs.chunk_event(s0, stop, wall_c)
        if do_srank:
            # explicit epilogue barrier (transfer-guard clean, like the
            # solo driver in experiment.py)
            srank = jax.device_get(out["srank"])
            for m in range(self.n_members):
                if self.done[m] or m in skip:
                    continue
                self.sranks[m].append(int(srank[m]))
                self._obs[m].log_event("srank", step=stop,
                                       srank=int(srank[m]))
        if do_eval:
            rets, scal = jax.device_get((out["eval"], out["scal"]))
            rets = np.asarray(rets)                     # (M, episodes)
            for m in range(self.n_members):
                if self.done[m] or m in skip:
                    continue
                ret = float(rets[m].mean())
                scalars = {k: float(v[m]) for k, v in scal.items()}
                self.returns[m].append(ret)
                self.eval_steps[m].append(stop)
                self._last_metrics[m] = scalars
                self._rows[m].append({"step": stop, "return": ret,
                                      **scalars})
                self._obs[m].log_eval(stop, ret, scalars)
                if progress:
                    progress(self.labels[m], stop, ret)
            if stop_at_return is not None:
                for m in range(self.n_members):
                    if (not self.done[m] and self.returns[m]
                            and self.returns[m][-1] >= stop_at_return):
                        self.done[m] = True
                        self._obs[m].log_event(
                            "early_stop", step=stop,
                            ret=self.returns[m][-1],
                            threshold=float(stop_at_return))

    # ------------------------------------------------------------- guarding
    def attach_guard(self, store) -> None:
        """Attach a ``repro.guard.store.DurableStore`` of FLEET checkpoints
        (``Fleet.save`` payloads) — the rollback source for
        guard.policy='rollback'."""
        self._guard_store = store

    def _guard_check(self, s0: int, stop: int, do_srank: bool, out) -> list:
        """Run per-member health checks over one segment's outputs. Done
        members are exempt: their carries were frozen at the segment end,
        so the throwaway outputs vmap computed for them are not theirs."""
        viol: list = []
        hstream = (jax.device_get(out["stream"]) if "stream" in out
                   else None)
        for m in range(self.n_members):
            if self.done[m]:
                continue
            mm = self._guard_members[m]
            if hstream is not None:
                viol += mm.check_stream(
                    s0, {k: np.asarray(v)[:stop - s0, m]
                         for k, v in hstream.items()}, member=m)
            if do_srank and self._guard.spec.srank_collapse:
                series = self.sranks[m] + [int(np.asarray(out["srank"])[m])]
                viol += mm.check_srank(stop, series, member=m)
        viol += [v for v in self._guard.check_member_params(
                     stop, self._fls.agent["params"])
                 if not self.done[v.member]]
        return viol

    def _guard_recover_members(self, violations: list,
                               stop: int) -> frozenset:
        """Apply the fleet guard policy: halt raises; rollback restores the
        violating MEMBERS from the newest good fleet checkpoint through the
        segment-end ``_tree_where`` select — one leaf-wise where against
        the restored stacked state — so healthy neighbors' bits are never
        touched. Rolled-back members get ``fold_in``-perturbed keys and
        continue with the fleet from their older state (histories keep
        their real past evals; the rollback is logged per member). Returns
        the violating member set for ``_record`` to skip."""
        mon = self._guard
        for v in violations:
            d = v.as_dict()
            m = d.pop("member", 0)
            self._obs[m].log_event("guard_violation", **d)
        bad = frozenset(v.member for v in violations)
        try:
            if mon.spec.policy == "halt":
                raise GuardViolation(
                    f"guard: halt on {violations[0].reason} at step "
                    f"{violations[0].step} (member(s) {sorted(bad)})",
                    violations, mon.recoveries)
            ordinal = mon.spend_recovery(violations)
            store = self._guard_store
            if store is None:
                raise GuardViolation(
                    "guard.policy='rollback' needs a DurableStore — call "
                    "Fleet.attach_guard(store) (the supervisor does this "
                    "automatically)", violations, mon.recoveries)
            path = store.restore_latest(
                on_bad=lambda b: self._obs[0].log_event(
                    "guard_bad_checkpoint", step=stop, path=str(b.path),
                    reason=b.reason))
            if path is None:
                raise GuardViolation(
                    f"guard rollback: no good checkpoint in {store.dir}",
                    violations, mon.recoveries)
        except GuardViolation:
            for obs in self._obs:
                obs.drain()
            raise
        typed = self._state_template()
        tree = ckpt.restore(store.payload(path),
                            {_CKPT_KEY: _unkey_abstract(typed)})
        good = _rekey(tree[_CKPT_KEY], typed)
        good = good._replace(key=jax.vmap(
            lambda k: jax.random.fold_in(k, ordinal))(good.key))
        mask = np.zeros(self.n_members, bool)
        mask[sorted(bad)] = True
        self._fls = _tree_where(jnp.asarray(mask), good, self._fls)
        from_step = DurableStore.step_of(path)
        for m in sorted(bad):
            self._obs[m].log_event(
                "guard_rollback", step=stop, recovery=ordinal,
                detected=violations[0].step, rolled_back_to=from_step,
                reason=violations[0].reason)
            self._obs[m].drain()
        return bad

    def set_done(self, members, value: bool = True) -> None:
        """Freeze (or unfreeze) members by index list or ``(M,)`` bool
        mask. Frozen members' carries stay untouched through subsequent
        chunks — unfreezing resumes them bit-exactly."""
        members = np.asarray(members)
        if members.dtype == bool:
            if members.shape != (self.n_members,):
                raise SpecError(f"done mask shape {members.shape} != "
                                f"({self.n_members},)")
            self.done = members.copy() if value else ~members
        else:
            self.done[members] = value

    # --------------------------------------------------------- PBT stretch
    def exploit_explore(self, *, fraction: float = 0.25,
                        noise_scale: float = 0.0,
                        scores: Optional[Sequence[float]] = None) -> dict:
        """Truncation selection on the member axis (PBT exploit/explore).

        Ranks members by ``scores`` (default: each member's latest eval
        return), copies the AGENT state (params/opt/step) of the top
        ``fraction`` onto the bottom ``fraction``, and — when
        ``noise_scale`` > 0 — perturbs the copied params multiplicatively
        with per-member-key Gaussian noise (explore). Actors, replay and
        the member's own PRNG key stay untouched, so an overwritten member
        keeps learning from its own experience stream. Done members are
        never overwritten or copied from. Returns a report dict
        ``{"copied": {loser_label: winner_label}, "scores": [...]}``.
        """
        if not 0.0 < fraction <= 0.5:
            raise SpecError(f"exploit_explore fraction={fraction} must be "
                            f"in (0, 0.5]")
        self._ensure_init()
        if scores is None:
            scores = [r[-1] if r else -np.inf for r in self.returns]
        scores = np.asarray(scores, np.float64)
        if scores.shape != (self.n_members,):
            raise SpecError(f"scores shape {scores.shape} != "
                            f"({self.n_members},)")
        eligible = np.nonzero(~self.done & np.isfinite(scores))[0]
        k = min(int(round(self.n_members * fraction)), len(eligible) // 2)
        if k < 1:
            return {"copied": {}, "scores": scores.tolist()}
        order = eligible[np.argsort(scores[eligible])]
        losers, winners = order[:k], order[-k:][::-1]
        src = np.arange(self.n_members)
        src[losers] = winners
        explore = np.zeros(self.n_members, bool)
        explore[losers] = True

        fls = self._fls
        agent = jax.tree_util.tree_map(lambda x: x[jnp.asarray(src)],
                                       fls.agent)
        if noise_scale > 0.0:
            keys = jax.vmap(lambda kk: jax.random.split(kk, 2))(fls.key)
            next_key = _tree_where(jnp.asarray(explore), keys[:, 0],
                                   fls.key)
            mask = jnp.asarray(explore, jnp.float32)
            leaves, treedef = jax.tree_util.tree_flatten(agent["params"])
            perturbed = []
            for i, leaf in enumerate(leaves):
                kn = jax.vmap(lambda kk: jax.random.fold_in(kk, i))(
                    keys[:, 1])
                noise = jax.vmap(
                    lambda kk, shp=leaf.shape[1:]:
                    jax.random.normal(kk, shp))(kn)
                m = mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
                perturbed.append(leaf * (1.0 + noise_scale * m * noise))
            agent = dict(agent,
                         params=jax.tree_util.tree_unflatten(treedef,
                                                             perturbed))
            fls = fls._replace(key=next_key)
        self._fls = fls._replace(agent=agent)
        copied = {self.labels[lo]: self.labels[wi]
                  for lo, wi in zip(losers, winners)}
        for lo, wi in zip(losers, winners):
            self._obs[lo].log_event("exploit", step=self.step,
                                    copied_from=self.labels[wi],
                                    noise_scale=float(noise_scale))
        return {"copied": copied, "scores": scores.tolist()}

    # ------------------------------------------------------------ results
    def results(self) -> List[RunResult]:
        """One cumulative ``RunResult`` per member (fleet order). The wall
        time is the shared fleet wall clock — members run in lockstep."""
        out = []
        for m in range(self.n_members):
            metrics = dict(self._last_metrics[m],
                           host_dispatches=float(self.trainer.dispatches))
            out.append(RunResult(
                returns=list(self.returns[m]),
                eval_steps=list(self.eval_steps[m]),
                sranks=list(self.sranks[m]), metrics=metrics,
                param_count=getattr(self.trainer, "n_params", 0),
                wall_time_s=self._wall))
        return out

    def metrics(self, member: int):
        """The RunResult-style eval rows of one member."""
        return iter([dict(r) for r in self._rows[member]])

    @property
    def obs(self) -> List[ObsRun]:
        return self._obs

    def close(self) -> None:
        for obs in self._obs:
            obs.close()

    # ------------------------------------------------------ checkpointing
    def save(self, path: str) -> None:
        """Full fleet state -> one checkpoint via ``repro.checkpoint.ckpt``
        (the member axis is just another leaf dimension). Drains the device
        program and the per-member obs writers first, like
        ``Experiment.save``."""
        self._ensure_init()
        jax.block_until_ready(self._fls)
        jax.effects_barrier()
        for obs in self._obs:
            obs.drain()
        state = {
            "specs": [s.to_dict() for s in self.specs],
            "labels": self.labels, "points": self.points,
            "step": self.step, "done": self.done.tolist(),
            "returns": self.returns, "eval_steps": self.eval_steps,
            "sranks": self.sranks, "rows": self._rows,
            "last_metrics": self._last_metrics,
            "wall_time_s": self._wall,
            "n_params": int(getattr(self.trainer, "n_params", 0)),
            "dispatches": int(self.trainer.dispatches),
            "obs": [obs.state() for obs in self._obs],
        }
        ckpt.save(path, {_CKPT_KEY: _unkey(self._fls)},
                  metadata={_CKPT_KEY: state})
        for obs in self._obs:
            obs.log_event("save", step=self.step, path=str(path))
            obs.drain()

    @classmethod
    def restore(cls, path: str) -> "Fleet":
        """Rebuild a fleet from ``save`` output. The restore template is
        abstract (``jax.eval_shape`` over the vmapped init — ``ckpt.restore``
        accepts ShapeDtypeStruct leaves), so restoring compiles nothing."""
        meta = ckpt.load_metadata(path)
        if meta is None or _CKPT_KEY not in meta:
            raise FileNotFoundError(
                f"{path}: no fleet-bearing checkpoint metadata "
                f"({path}.meta.json) — was this saved by Fleet.save?")
        st = meta[_CKPT_KEY]
        fl = cls([ExperimentSpec.from_dict(d) for d in st["specs"]],
                 labels=list(st["labels"]), points=st.get("points"))
        typed = fl._state_template()
        tree = ckpt.restore(path, {_CKPT_KEY: _unkey_abstract(typed)})
        fl._fls = _rekey(tree[_CKPT_KEY], typed)
        fl.step = int(st["step"])
        fl.done = np.asarray(st["done"], bool)
        fl.returns = [[float(r) for r in rs] for rs in st["returns"]]
        fl.eval_steps = [[int(s) for s in ss] for ss in st["eval_steps"]]
        fl.sranks = [[int(s) for s in ss] for ss in st["sranks"]]
        fl._rows = [[dict(r) for r in rs] for rs in st.get("rows", [])] \
            or [[] for _ in fl.specs]
        fl._last_metrics = [dict(m) for m in st.get("last_metrics", [])] \
            or [{} for _ in fl.specs]
        fl._wall = float(st.get("wall_time_s", 0.0))
        fl.trainer.n_params = int(st["n_params"])
        fl.trainer.dispatches = int(st.get("dispatches", 0))
        for obs, ost in zip(fl._obs, st.get("obs", [])):
            obs.load_state(ost)
            obs.log_event("restore", step=fl.step, path=str(path))
            obs.drain()
        return fl


# ------------------------------------------------------------------ sweep

@dataclasses.dataclass
class MemberResult:
    """One grid member's outcome: where it came from and what it scored."""
    label: str
    point: Dict[str, Any]           # the override()s that define the member
    seed: int
    result: RunResult


class Sweep:
    """A grid of experiment variants, partitioned into vmapped fleets.

    ``from_grid`` expands ``axis`` x ``seeds`` into member specs, groups
    them by compiled signature (spec modulo seed) and builds one ``Fleet``
    per group — so a width sweep becomes per-width sub-fleets while a pure
    seed battery is a single fleet. ``partition`` reports the grouping.
    ``run``/``save``/``restore``/``results`` fan out over the fleets.
    """

    def __init__(self, fleets: Sequence[Fleet],
                 order: Optional[Sequence[tuple]] = None):
        if not fleets:
            raise SpecError("Sweep needs at least one fleet")
        self.fleets = list(fleets)
        # grid order as (fleet_idx, member_idx); default: fleet order
        self._order = [tuple(o) for o in order] if order is not None else [
            (fi, mi) for fi, fl in enumerate(self.fleets)
            for mi in range(fl.n_members)]

    @classmethod
    def from_grid(cls, base, axis=None, seeds: int = 1,
                  **overrides) -> "Sweep":
        """Build a sweep over ``base`` (an ``ExperimentSpec`` or a
        ``repro.rl.presets`` name).

        ``axis`` is either a dict of ``override()`` key -> list of values
        (full cartesian product) or an explicit list of override dicts
        (irregular grids). ``seeds`` replicates every grid point with
        ``execution.seed`` = base seed + 0..seeds-1. Extra ``overrides``
        apply to the base spec first."""
        from repro.rl import presets
        spec = presets.get(base) if isinstance(base, str) else base
        if overrides:
            spec = spec.override(**overrides)
        if isinstance(axis, Mapping):
            keys = list(axis)
            points = [dict(zip(keys, vals))
                      for vals in itertools.product(*(axis[k]
                                                      for k in keys))]
        else:
            points = [dict(p) for p in axis] if axis else [{}]
        if not points:
            points = [{}]
        for p in points:
            if any(k in ("seed", "execution.seed") for k in p):
                raise SpecError("put seeds on the seeds= axis, not in "
                                "axis= (fleet members batch over seeds)")
        _positive_seeds(seeds)
        base_seed = spec.execution.seed

        members = []                      # (sig_json, spec, label, point)
        for point in points:
            pspec = spec.override(**point) if point else spec
            ptag = ",".join(f"{k}={v}" for k, v in point.items())
            for si in range(seeds):
                mspec = pspec.override(seed=base_seed + si)
                label = (ptag + "," if ptag else "") + f"seed={base_seed+si}"
                sig = json.dumps(_fleet_signature(mspec), sort_keys=True)
                members.append((sig, mspec, label, point))

        groups: Dict[str, List[tuple]] = {}
        for sig, mspec, label, point in members:
            groups.setdefault(sig, []).append((mspec, label, point))
        fleets = [Fleet([m[0] for m in g], labels=[m[1] for m in g],
                        points=[m[2] for m in g])
                  for g in groups.values()]
        # recover grid order through the per-fleet member positions
        pos = {(id_sig, label): (fi, mi)
               for fi, (id_sig, g) in enumerate(groups.items())
               for mi, (_, label, _) in enumerate(g)}
        order = [pos[(sig, label)] for sig, _, label, _ in members]
        return cls(fleets, order=order)

    # ------------------------------------------------------------- surface
    @property
    def n_members(self) -> int:
        return sum(fl.n_members for fl in self.fleets)

    @property
    def partition(self) -> List[List[str]]:
        """Member labels grouped by fleet — the compiled-shape partition
        ``from_grid`` chose (one entry per compiled program)."""
        return [list(fl.labels) for fl in self.fleets]

    def describe(self) -> str:
        lines = [f"sweep: {self.n_members} members in {len(self.fleets)} "
                 f"fleet(s) (one compiled program each)"]
        for fi, fl in enumerate(self.fleets):
            lines.append(f"  fleet {fi}: {fl.n_members} member(s) — "
                         f"{', '.join(fl.labels)}")
        return "\n".join(lines)

    def run(self, steps: Optional[int] = None, **kwargs) \
            -> List[MemberResult]:
        """``Fleet.run`` on every fleet in partition order; returns
        ``results()`` (grid order)."""
        for fl in self.fleets:
            fl.run(steps, **kwargs)
        return self.results()

    def results(self) -> List[MemberResult]:
        """Per-member results in the ORIGINAL grid order (axis product
        x seeds), regardless of how the partition grouped them."""
        per_fleet = [fl.results() for fl in self.fleets]
        out = []
        for fi, mi in self._order:
            fl = self.fleets[fi]
            out.append(MemberResult(
                label=fl.labels[mi], point=dict(fl.points[mi]),
                seed=int(fl.seeds[mi]), result=per_fleet[fi][mi]))
        return out

    def close(self) -> None:
        for fl in self.fleets:
            fl.close()

    def exploit_explore(self, **kwargs) -> List[dict]:
        """``Fleet.exploit_explore`` per fleet (PBT cannot copy params
        across fleets — different compiled shapes)."""
        return [fl.exploit_explore(**kwargs) for fl in self.fleets]

    # ------------------------------------------------------ checkpointing
    def save(self, directory: str) -> None:
        """One fleet checkpoint per sub-fleet + a ``sweep.json`` manifest
        under ``directory``."""
        d = Path(directory)
        d.mkdir(parents=True, exist_ok=True)
        for fi, fl in enumerate(self.fleets):
            fl.save(str(d / f"fleet_{fi:03d}.npz"))
        (d / "sweep.json").write_text(json.dumps(
            {"version": 1, "fleets": len(self.fleets),
             "order": [list(o) for o in self._order]}, indent=1))

    @classmethod
    def restore(cls, directory: str) -> "Sweep":
        d = Path(directory)
        manifest = d / "sweep.json"
        if not manifest.exists():
            raise FileNotFoundError(f"{manifest}: not a Sweep.save output")
        m = json.loads(manifest.read_text())
        fleets = [Fleet.restore(str(d / f"fleet_{fi:03d}.npz"))
                  for fi in range(int(m["fleets"]))]
        return cls(fleets, order=[tuple(o) for o in m["order"]])


def _positive_seeds(seeds) -> None:
    if not isinstance(seeds, (int, np.integer)) or isinstance(seeds, bool) \
            or seeds < 1:
        raise SpecError(f"seeds={seeds!r} must be an int >= 1")
