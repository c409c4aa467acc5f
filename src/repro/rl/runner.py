"""Training runner: glues algorithms, OFENet, replay and the Ape-X actor pool.

The typed entry point is ``repro.rl.experiment`` (``ExperimentSpec`` +
resumable ``Experiment`` handle); this module keeps the ``Trainer`` engine
they drive. ``Trainer`` consumes the spec tree natively (the flat
``RunConfig``/``run_training`` surface is GONE — the former deprecation
shims now raise with a porting hint). Every paper ablation is a spec field:

* ``network.connectivity``   — mlp | resnet | densenet | d2rl   (Fig. 5)
* ``network.num_units/_layers`` — width/depth study             (Figs. 1/3/4)
* ``ofenet.enabled``         — decoupled representation          (Figs. 6/7)
* ``execution.distributed``  — Ape-X actor pool vs 1-step loop   (Figs. 8/12)
* ``algo``                   — sac | td3                         (Fig. 9)
* ``replay.prioritized``     — PER vs uniform replay
* ``network.block_backend``  — "jnp" | "fused": route every MLP block
  (actor, twin critics, OFENet online/target) through the fused streaming
  DenseNet-stack kernel (``kernels/dense_block/stack.py``, custom VJP) so
  the scanned superstep trains through it; "jnp" is the concat loop
* ``replay.backend``         — host (NumPy sum-tree) | device (repro.replay)
  with ``replay.kernel`` picking the device sum-tree impl ("xla" | "pallas")
* ``replay.n_step``          — Ape-X n-step returns (1 | 3), computed on
  device in the replay add path (repro.replay.store.nstep_push)
* ``obs``                    — in-loop telemetry (``repro.obs``): when
  ``obs.enabled``, the scan body additionally emits every scalar training
  metric per step as stacked scan outputs (``chunk_fn``'s
  ``out["stream"]``), flushed/downsampled on the host in the chunk
  epilogue — the body stays uniform across chunk lengths and obs knobs, so
  the bitwise-resume contract is preserved with obs on or off, and
  enabling obs does not change training outputs bitwise (tests/test_obs).
  ``obs.grad_norms`` threads ``grad_norms=True`` into the algorithm
  configs (sac/td3 grad-norm + update-ratio taps).
* ``execution.loop``         — "python" | "scan":

  The training loop is built around a functional ``TrainLoopState`` and a
  pure superstep that fuses collect -> n-step -> add -> sample -> update ->
  priority-refresh. ``loop="python"`` dispatches the superstep's pieces one
  host call at a time (the debuggable legacy shape, ~5 dispatches per
  gradient step). ``loop="scan"`` drives the SAME superstep with
  ``jax.lax.scan`` in ``eval_every``-sized chunks — evaluation (a vmapped
  rollout scan) folds into the same jitted chunk, so ``run_training`` issues
  ``total_steps / eval_every + O(1)`` host dispatches total (plus
  ``total_steps / srank_every`` when srank instrumentation is on: chunks
  also stop at srank points so both drivers record identical steps; counted
  in ``RunResult.metrics["host_dispatches"]``; throughput:
  benchmarks/loop_fusion.py). A chunk is ONE scan over ALL its supersteps
  with the last step's metrics/batch carried through the scan carry — the
  superstep only ever compiles as the scan body, so any re-chunking of the
  same step sequence is bitwise-identical (the resume-anywhere guarantee;
  see ``Trainer.chunk_fn``). The host replay backend rides the scanned
  superstep through ordered ``io_callback``s, so both backends are
  seed-for-seed identical across ``loop=`` choices.

* ``execution.mesh_shards``  — >0 routes the superstep through the
  mesh-sharded Ape-X wiring (``replay.collect_and_add_sharded`` +
  ``sharded_replay_sample``): actors and replay shards live on the mesh
  ``data`` axis (``launch.mesh.make_actor_mesh``), transitions never leave
  their shard, and the learner consumes one coherent cross-shard batch.
  Requires ``replay.backend="device"``.

``RunResult.metrics`` also surfaces the priority-staleness distribution of
the last sampled batch (``staleness_mean/p50/max`` = learner step - add
step) on the device backend; the host backend does not stamp rows, so the
staleness keys are omitted there.
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Any, Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro.common import tree_size
from repro.core.effective_rank import effective_rank
from repro.kernels import default_interpret
from repro.obs.trace import annotate
from repro.launch.mesh import make_actor_mesh, replay_shards
from repro.replay import (DeviceReplayConfig, nstep_emit_flat, nstep_init,
                          replay_add, replay_init, replay_sample,
                          replay_update)
from repro.replay import sharded as replay_sharded
from repro.rl import apex, policy as policy_mod
from repro.rl import replay as replay_mod, sac as sac_mod, td3 as td3_mod
from repro.rl.envs import EnvSpec, eval_returns, make_env

_TRANSITION_FIELDS = ("obs", "act", "rew", "next_obs", "done")


_REMOVED = (
    "{name} was removed: the RunConfig deprecation period ended (it warned "
    "since the ExperimentSpec API landed). Build a spec instead — the flat "
    "field names still work as override aliases:\n"
    "    from repro.rl import Experiment, ExperimentSpec\n"
    "    spec = ExperimentSpec().override(num_units=256, "
    "replay_backend='device', loop='scan')\n"
    "    res = Experiment.from_spec(spec).run(spec.execution.total_steps)\n"
    "or start from a repro.rl.presets entry.")


class RunConfig:
    """REMOVED — the flat config's deprecation warning is now an error."""

    def __init__(self, *_a, **_k):
        raise RuntimeError(_REMOVED.format(name="RunConfig"))


def run_training(*_a, **_k):
    """REMOVED — the one-shot shim's deprecation warning is now an error."""
    raise RuntimeError(_REMOVED.format(name="run_training"))


def _build(spec, env: EnvSpec):
    """Algorithm pieces for a (duck-typed) ``ExperimentSpec``: the algo
    config with OFENet/obs knobs threaded in, plus init/update fns. The
    act/eval policy functions live in ``repro.rl.policy`` (the unified
    inference layer) — the former four duck-typed closures are gone."""
    acfg = policy_mod.algo_config(spec, env)
    if spec.algo == "sac":
        return acfg, sac_mod.sac_init, sac_mod.sac_update
    return acfg, td3_mod.td3_init, td3_mod.td3_update


@dataclasses.dataclass
class RunResult:
    returns: List[float]
    eval_steps: List[int]
    sranks: List[int]
    metrics: Dict[str, float]
    param_count: int
    wall_time_s: float
    state: object = None             # only when run(keep_last=True)
    last_batch: object = None
    last_priorities: object = None   # final sampled-batch TD priorities

    @property
    def final_return(self) -> float:
        return float(np.mean(self.returns[-2:])) if self.returns else float("nan")

    @property
    def max_return(self) -> float:
        return float(np.max(self.returns)) if self.returns else float("nan")


class TrainLoopState(NamedTuple):
    """Everything the training loop threads between gradient steps — a pure
    pytree so the whole superstep can live inside ``jax.lax.scan``."""
    agent: Any       # algorithm state: params / opt / step
    actors: Any      # vectorized EnvState of the Ape-X actor pool
    nstep: Any       # per-actor n-step rollback ring (None when n_step == 1)
    replay: Any      # ReplayState (device/sharded) or an i32 token (host)
    key: jax.Array   # PRNG key, split once per superstep
    step: jax.Array  # completed learner steps (i32) — stamps replay adds


class Trainer:
    """Builds every jitted piece of the training loop once.

    ``py_step`` runs one superstep as separate host dispatches (the legacy
    debuggable loop); ``chunk_fn`` compiles ``n`` supersteps + optional
    evaluation/srank into ONE program: a single ``jax.lax.scan`` whose carry
    threads the last step's metrics/batch out, so the superstep compiles
    identically for every chunk length (bitwise resume at any step). Both
    drivers share the same pure ops and PRNG schedule, so they are
    seed-for-seed interchangeable. ``dispatches`` counts host->device
    program launches issued through this Trainer (the parity test's
    traced-call counter).
    """

    def __init__(self, spec, mesh=None):
        # consumes a typed ExperimentSpec natively (duck-typed by field
        # access, so this module never imports repro.rl.experiment); the
        # flat RunConfig view is gone
        self.spec = spec
        x, r = spec.execution, spec.replay
        # loop-hot scalars lifted off the spec tree once
        self.n_step = r.n_step
        self.batch_size = x.batch_size
        self.seed = x.seed
        self.warmup_steps = x.warmup_steps
        self.eval_episodes = spec.eval.episodes
        self.srank_every = spec.eval.srank_every
        # the guard consumes the same stacked scalar stream obs does —
        # emitting it is bitwise-invisible to training (tests/test_obs.py),
        # so forcing it on for detection keeps guarded == unguarded bitwise.
        # getattr: bare specs in unit tests may predate the guard section.
        g = getattr(spec, "guard", None)
        self.obs_stream = spec.obs.enabled or bool(g is not None
                                                   and g.enabled)
        self.dispatches = 0
        self._chunks: Dict[tuple, Callable] = {}
        self.env = env = make_env(spec.env)
        self.acfg, self.init_fn, self.update_fn = _build(spec, env)
        # ONE inference surface for collect, eval and serving: the base
        # Policy handle (params bound per call site). Its raw act fn drives
        # collection inside the traced superstep; eval and external serving
        # clients go through with_params (shared jit cache).
        self.policy0 = policy_mod.Policy.from_algo(spec.algo, self.acfg,
                                                   env_name=spec.env)
        self.n_actors = x.n_actors
        self.gamma = self.acfg.gamma

        if mesh is None and x.mesh_shards > 0:
            mesh = make_actor_mesh(x.mesh_shards)
        self.mesh = mesh
        self.use_device = r.backend == "device"
        if mesh is not None:
            if not self.use_device:
                raise ValueError("mesh_shards requires replay.backend="
                                 "'device'")
            shards = replay_shards(mesh)
            if (self.n_actors % shards or x.batch_size % shards
                    or r.capacity % shards):
                raise ValueError(
                    f"mesh_shards={shards} must divide n_actors="
                    f"{self.n_actors}, batch_size={x.batch_size} and "
                    f"replay_capacity={r.capacity}")
        if not self.use_device and r.backend != "host":
            raise ValueError(r.backend)

        self._train_policy = self.policy0.act_fn
        self._rand_policy = apex.random_policy(env.act_dim)

        # ------------------------------------------------ replay backends
        if self.use_device:
            shards = replay_shards(mesh) if mesh is not None else 1
            self.dcfg = DeviceReplayConfig(
                capacity=r.capacity // shards, obs_dim=env.obs_dim,
                act_dim=env.act_dim, uniform=not r.prioritized,
                backend=r.kernel,
                interpret=default_interpret(),
                n_step=r.n_step)
            self.buffer = None
        else:
            buf_cls = (replay_mod.PrioritizedReplay if r.prioritized
                       else replay_mod.UniformReplay)
            self.buffer = buf_cls(r.capacity, env.obs_dim,
                                  env.act_dim, n_step=r.n_step)
            self.rng = np.random.default_rng(x.seed)
            self._host_fields = list(_TRANSITION_FIELDS)
            if r.n_step > 1:
                self._host_fields.append("disc")

        # ------------------------------------------- jitted python-loop ops
        w = self._count
        self._update_j = w(jax.jit(self._op_update))
        self.eval_j = w(jax.jit(lambda params, k: eval_returns(
            env, self.policy0.with_params(params), k, self.eval_episodes)))
        if self.use_device:
            self._collect_add_j = w(jax.jit(partial(
                self._op_collect_add, self._train_policy, steps=1, drop=0)))
            self._sample_j = w(jax.jit(self._op_sample))
            self._update_prio_j = w(jax.jit(self._op_update_prio))
        else:
            self._collect_emit_j = w(jax.jit(partial(
                self._collect_emit, self._train_policy, steps=1, drop=0)))

    # ------------------------------------------------------------- helpers
    def policy(self, params=None) -> "policy_mod.Policy":
        """The unified inference handle (``repro.rl.policy.Policy``) for
        this Trainer's algorithm/network, bound to ``params`` when given.
        Eval, the serving engine and external clients all act through it."""
        return self.policy0 if params is None \
            else self.policy0.with_params(params)

    def _count(self, fn):
        # functools.wraps keeps the jitted fn reachable (``__wrapped__``)
        # for AOT lowering, e.g. to inspect a chunk's compiled HLO
        @wraps(fn)
        def wrapped(*args, **kwargs):
            self.dispatches += 1
            return fn(*args, **kwargs)
        return wrapped

    def _canonical_shardings(self):
        """The mesh layout every TrainLoopState must keep: actor/replay/
        n-step leaves split on ``data`` (leading axis), agent/key/step
        replicated. Pinning both the initial state (device_put) and the
        chunk outputs (with_sharding_constraint) keeps the jitted chunk's
        signature stable — without it the second call recompiles against
        the first call's drifted output shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return (NamedSharding(self.mesh, P("data")),
                NamedSharding(self.mesh, P()))

    def _pin(self, ls: TrainLoopState, put=False) -> TrainLoopState:
        if self.mesh is None:
            return ls
        data, rep = self._canonical_shardings()
        if put:
            place = jax.device_put
        else:
            # with_sharding_constraint can't express a rank-1 spec against a
            # typed PRNG key's raw u32[..., 2] shape — let those propagate
            def place(x, s):
                if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
                    return x
                return jax.lax.with_sharding_constraint(x, s)
        tm = jax.tree_util.tree_map
        return TrainLoopState(
            tm(lambda x: place(x, rep), ls.agent),
            tm(lambda x: place(x, data), ls.actors),
            tm(lambda x: place(x, data), ls.nstep),
            tm(lambda x: place(x, data), ls.replay),
            place(ls.key, rep), place(ls.step, rep))

    def _collect_emit(self, policy, params, actors, nstate, key, *,
                      steps: int, drop: int):
        """collect ``steps`` env steps and roll them through the n-step ring
        (identity for n_step == 1); returns store-schema transition rows."""
        with jax.named_scope("repro.collect"):
            actors, trs = apex.collect(self.env, policy, params, actors,
                                       steps, key)
            if self.n_step == 1:
                return actors, nstate, {k: trs[k]
                                        for k in _TRANSITION_FIELDS}
            nstate, flat = nstep_emit_flat(self.n_step, self.gamma, nstate,
                                           trs, steps, drop)
            return actors, nstate, flat

    # ------------------------------------------------- device backend ops
    def _op_collect_add(self, policy, params, actors, nstate, rstate, key,
                        step, *, steps: int, drop: int):
        if self.mesh is not None:
            # one fused call collects and adds on each shard: the add's
            # scope covers both
            with jax.named_scope("repro.replay.add"):
                if self.n_step > 1:
                    return replay_sharded.collect_and_add_sharded(
                        self.env, policy, self.mesh, self.dcfg, params,
                        actors, steps, key, rstate, nstep_state=nstate,
                        gamma=self.gamma, step=step, drop=drop)
                actors, rstate = replay_sharded.collect_and_add_sharded(
                    self.env, policy, self.mesh, self.dcfg, params, actors,
                    steps, key, rstate, step=step)
                return actors, nstate, rstate
        actors, nstate, flat = self._collect_emit(
            policy, params, actors, nstate, key, steps=steps, drop=drop)
        with jax.named_scope("repro.replay.add"):
            return actors, nstate, replay_add(self.dcfg, rstate, flat,
                                              step=step)

    def _op_sample(self, rstate, key, step):
        with jax.named_scope("repro.replay.sample"):
            if self.mesh is not None:
                batch, idx, weights = replay_sharded.sharded_replay_sample(
                    self.dcfg, self.mesh, rstate, key, self.batch_size)
            else:
                batch, idx, weights = replay_sample(self.dcfg, rstate, key,
                                                    self.batch_size)
            staleness = (step - batch.pop("add_step")).astype(jnp.float32)
        batch["weight"] = weights
        return batch, idx, staleness

    def _op_update(self, agent, batch, key):
        with jax.named_scope("repro.update"):
            return self.update_fn(agent, self.acfg, batch, key)

    def _op_update_prio(self, rstate, idx, priorities):
        with jax.named_scope("repro.replay.refresh"):
            if self.mesh is not None:
                return replay_sharded.sharded_replay_update(
                    self.dcfg, self.mesh, rstate, idx, priorities)
            return replay_update(self.dcfg, rstate, idx, priorities)

    # --------------------------------------------- host backend callbacks
    def _cb_add(self, *arrs):
        with annotate("repro.replay.host_add"):
            self.buffer.add_batch(dict(zip(self._host_fields,
                                           [np.asarray(a) for a in arrs])))
        return np.int32(0)

    def _cb_sample(self):
        with annotate("repro.replay.host_sample"):
            batch, idx, weights = self.buffer.sample(self.batch_size,
                                                     self.rng)
        return (tuple(batch[f].astype(np.float32)
                      for f in self._host_fields)
                + (idx.astype(np.int32), weights.astype(np.float32)))

    def _cb_update(self, idx, priorities):
        with annotate("repro.replay.host_update_prio"):
            self.buffer.update_priorities(np.asarray(idx),
                                          np.asarray(priorities))
        return np.int32(0)

    def _host_sample_shapes(self):
        env, bs = self.env, self.batch_size
        dims = {"obs": (bs, env.obs_dim), "act": (bs, env.act_dim),
                "rew": (bs,), "next_obs": (bs, env.obs_dim), "done": (bs,),
                "disc": (bs,)}
        return (tuple(jax.ShapeDtypeStruct(dims[f], jnp.float32)
                      for f in self._host_fields)
                + (jax.ShapeDtypeStruct((bs,), jnp.int32),
                   jax.ShapeDtypeStruct((bs,), jnp.float32)))

    # ------------------------------------------------------ the superstep
    def _device_step(self, ls, collect_add, sample, update, update_prio):
        """The device-replay superstep over injectable ops — the scan body
        passes the pure ops, the python driver their per-op jitted twins."""
        key, kc, ks, ku = jax.random.split(ls.key, 4)
        actors, nstate, rstate = collect_add(ls.agent["params"], ls.actors,
                                             ls.nstep, ls.replay, kc,
                                             ls.step)
        batch, idx, staleness = sample(rstate, ks, ls.step)
        agent, metrics = update(ls.agent, batch, ku)
        rstate = update_prio(rstate, idx, metrics["priorities"])
        return self._finish_step(ls, agent, actors, nstate, rstate, key,
                                 staleness, metrics, batch)

    def _finish_step(self, ls, agent, actors, nstate, rstate, key,
                     staleness, metrics, batch):
        """Shared superstep tail: staleness metrics + next TrainLoopState.
        Keeping this single keeps the scan/python drivers seed-exact.
        ``staleness=None`` (host replay: rows carry no add-step stamps)
        omits the staleness keys instead of reporting a bogus sentinel."""
        if staleness is not None:
            with jax.named_scope("repro.replay.sample"):
                metrics = dict(metrics,
                               staleness_mean=staleness.mean(),
                               staleness_p50=jnp.median(staleness),
                               staleness_max=staleness.max())
        ls = TrainLoopState(agent, actors, nstate, rstate, key, ls.step + 1)
        return ls, metrics, batch

    def _superstep(self, ls: TrainLoopState):
        """One pure collect->add->sample->update->refresh step — the scan
        body. Host replay rides along via ordered io_callbacks on the SAME
        buffer/rng the python loop uses, so the two loops stay seed-exact.
        Each phase runs under its own ``jax.named_scope`` (``repro.collect``,
        ``repro.replay.add``, ``repro.replay.sample``, ``repro.update``,
        ``repro.replay.refresh``): op metadata only, so a profiler trace
        can put device time on phases while the program stays the same."""
        if self.use_device:
            return self._device_step(
                ls,
                partial(self._op_collect_add, self._train_policy, steps=1,
                        drop=0),
                self._op_sample, self._op_update, self._op_update_prio)
        key, kc, ks, ku = jax.random.split(ls.key, 4)
        actors, nstate, flat = self._collect_emit(
            self._train_policy, ls.agent["params"], ls.actors, ls.nstep, kc,
            steps=1, drop=0)
        with jax.named_scope("repro.replay.add"):
            io_callback(self._cb_add, jax.ShapeDtypeStruct((), jnp.int32),
                        *[flat[f] for f in self._host_fields], ordered=True)
        with jax.named_scope("repro.replay.sample"):
            out = io_callback(self._cb_sample, self._host_sample_shapes(),
                              ordered=True)
        batch = dict(zip(self._host_fields, out))
        idx, batch["weight"] = out[-2], out[-1]
        agent, metrics = self._op_update(ls.agent, batch, ku)
        with jax.named_scope("repro.replay.refresh"):
            io_callback(self._cb_update, jax.ShapeDtypeStruct((), jnp.int32),
                        idx, metrics["priorities"], ordered=True)
        return self._finish_step(ls, agent, actors, nstate, ls.replay, key,
                                 None, metrics, batch)

    # ----------------------------------------------------------- drivers
    def py_step(self, ls: TrainLoopState):
        """One superstep as separate host dispatches (loop="python")."""
        if self.use_device:
            return self._device_step(ls, self._collect_add_j, self._sample_j,
                                     self._update_j, self._update_prio_j)
        key, kc, ks, ku = jax.random.split(ls.key, 4)
        actors, nstate, flat = self._collect_emit_j(ls.agent["params"],
                                                    ls.actors, ls.nstep, kc)
        self.buffer.add_batch({k: np.asarray(v) for k, v in flat.items()})
        batch_np, idx, weights = self.buffer.sample(self.batch_size,
                                                    self.rng)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        batch["weight"] = jnp.asarray(weights)
        agent, metrics = self._update_j(ls.agent, batch, ku)
        self.buffer.update_priorities(idx, np.asarray(metrics["priorities"]))
        return self._finish_step(ls, agent, actors, nstate, ls.replay, key,
                                 None, metrics, batch)

    def chunk_fn(self, n_steps: int, do_eval: bool,
                 do_srank: bool = False) -> Callable:
        """``n_steps`` supersteps (+ optional eval) as ONE jitted program.

        The chunk is a single ``lax.scan`` over ALL ``n_steps`` supersteps;
        the last step's metrics and sampled batch ride the scan CARRY (seeded
        with zero templates the first iteration overwrites), so there is no
        trailing unrolled superstep. The superstep therefore only ever
        compiles as the scan body — one uniform HLO computation regardless of
        chunk length — which is what makes any re-chunking of the same step
        sequence (and hence save/restore at ANY step) bitwise-identical.
        srank and the final batch/priorities are computed from the carried
        outputs in the EPILOGUE, outside the scan — epilogue variation
        (eval/srank) cannot change how the body compiles, so ``do_eval`` /
        ``do_srank`` only select what the chunk returns. ``want_last`` is
        gone from the signature entirely (the last batch/priorities are
        always available from the carry), shrinking the compile-cache key
        space to (n_steps, do_eval, do_srank).

        With ``obs.enabled`` the scan body additionally stacks every scalar
        metric as a scan output — ``out["stream"]``, one ``(n_steps,)``
        array per scalar. The stream is emitted in FULL on every step and
        downsampled on the host (``repro.obs.stream.ObsRun.flush_chunk``),
        so the body's codegen stays uniform across obs knobs and chunk
        lengths: the scalars were already live in the carry, and stacking
        extra outputs cannot change the training computation — obs on/off
        is bitwise-identical (tests/test_obs.py)."""
        do_srank = do_srank and bool(self.srank_every)
        sig = (n_steps, do_eval, do_srank)
        if sig in self._chunks:
            return self._chunks[sig]

        def chunk(ls: TrainLoopState):
            _, m_t, b_t = jax.eval_shape(self._superstep, ls)
            zeros = partial(jax.tree_util.tree_map,
                            lambda s: jnp.zeros(s.shape, s.dtype))
            stream_keys = tuple(sorted(
                k for k, v in m_t.items() if v.ndim == 0)) \
                if self.obs_stream else ()

            def body(carry, _):
                c, _m, _b = carry
                nxt = self._superstep(c)
                ys = ({k: nxt[1][k] for k in stream_keys}
                      if stream_keys else None)
                return nxt, ys

            (ls, metrics, batch), ys = jax.lax.scan(
                body, (ls, zeros(m_t), zeros(b_t)), None, length=n_steps)
            out = {"scal": {k: v for k, v in metrics.items()
                            if getattr(v, "ndim", None) == 0},
                   "last": (batch, metrics["priorities"])}
            if stream_keys:
                out["stream"] = ys
            if do_srank:
                with jax.named_scope("repro.srank"):
                    out["srank"] = effective_rank(metrics["q_features"])
            if do_eval:
                key, ke = jax.random.split(ls.key)
                ls = ls._replace(key=key)
                with jax.named_scope("repro.eval"):
                    out["eval"] = eval_returns(
                        self.env,
                        self.policy0.with_params(ls.agent["params"]), ke,
                        self.eval_episodes)
            return self._pin(ls), out

        self._chunks[sig] = self._count(jax.jit(chunk))
        return self._chunks[sig]

    # ------------------------------------------------------- initial state
    def _fresh_state(self, seed=None):
        """Agent/actor/replay init (shapes + seed-derived values), WITHOUT
        the warmup collect. Returns the pre-warmup TrainLoopState and the
        warmup key (same PRNG schedule as the original monolithic init).

        ``seed`` overrides the spec seed and may be a traced int32 — the
        fleet driver (``repro.rl.sweep``) vmaps this over a member seed
        vector so a whole sweep initializes as one device program."""
        env = self.env
        key = jax.random.key(self.seed if seed is None else seed)
        key, k_init, k_actor = jax.random.split(key, 3)
        agent = self.init_fn(k_init, self.acfg)
        self.n_params = tree_size(agent["params"])
        actors = apex.init_actor_states(env, k_actor, self.n_actors)

        nstate = None
        if self.n_step > 1 and self.mesh is None:
            nstate = nstep_init(self.n_step, self.n_actors, env.obs_dim,
                                env.act_dim)
        key, kw = jax.random.split(key)
        step0 = jnp.zeros((), jnp.int32)

        if self.use_device:
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                shards = replay_shards(self.mesh)
                actors = jax.device_put(actors, NamedSharding(self.mesh,
                                                              P("data")))
                rstate = replay_sharded.sharded_replay_init(self.dcfg,
                                                            self.mesh)
                if self.n_step > 1:
                    nstate = replay_sharded.sharded_nstep_init(
                        self.mesh, self.n_step, self.n_actors // shards,
                        env.obs_dim, env.act_dim)
            else:
                rstate = replay_init(self.dcfg)
        else:
            rstate = jnp.zeros((), jnp.int32)   # order token placeholder
        return TrainLoopState(agent, actors, nstate, rstate, key, step0), kw

    def init_template(self) -> TrainLoopState:
        """A TrainLoopState with the exact structure/shapes/dtypes of a live
        one but no warmup executed — the checkpoint-restore template
        (repro.rl.experiment.Experiment.restore overwrites every leaf)."""
        ls, _ = self._fresh_state()
        return ls

    def init(self) -> TrainLoopState:
        """Agent/actor/replay init + random-policy warmup (paper A.4)."""
        ls, kw = self._fresh_state()
        warm = max(self.warmup_steps // self.n_actors, 1, self.n_step)
        drop = self.n_step - 1
        if self.use_device:
            warm_j = self._count(jax.jit(partial(
                self._op_collect_add, self._rand_policy, steps=warm,
                drop=drop)))
            actors, nstate, rstate = warm_j(ls.agent["params"], ls.actors,
                                            ls.nstep, ls.replay, kw, ls.step)
            ls = ls._replace(actors=actors, nstep=nstate, replay=rstate)
        else:
            warm_j = self._count(jax.jit(partial(
                self._collect_emit, self._rand_policy, steps=warm,
                drop=drop)))
            actors, nstate, flat = warm_j(ls.agent["params"], ls.actors,
                                          ls.nstep, kw)
            self.buffer.add_batch({k: np.asarray(v)
                                   for k, v in flat.items()})
            ls = ls._replace(actors=actors, nstep=nstate)
        return self._pin(ls, put=True)
