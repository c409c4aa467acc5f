"""Layered experiment API: typed spec tree + resumable ``Experiment`` handle.

The run surface for the paper's method is a tree of small, validated specs:

    ExperimentSpec
    ├── env / algo            task + algorithm ("pendulum", "sac" | "td3")
    ├── network:   NetworkSpec    width / depth / connectivity / activation /
    │                             block_backend  (Figs. 1/3/4/5/13)
    ├── ofenet:    OFENetSpec     decoupled representation  (Figs. 6/7)
    ├── replay:    ReplaySpec     sum-tree kernel / capacity / PER /
    │                             n-step
    ├── execution: ExecutionSpec  mesh shards / batch / steps /
    │                             Ape-X actor pool / seed
    ├── eval:      EvalSpec       eval cadence + srank instrumentation
    ├── obs:       ObsSpec        in-loop telemetry: metric stream cadence,
    │                             sinks, grad-norm taps, profiler trace
    └── guard:     GuardSpec      in-loop health guards (repro.guard):
                                  divergence detection + halt/skip/rollback

Every field is choice-checked at construction and unsupported combinations
are rejected with actionable messages (``SpecError``) instead of failing
deep inside jit — e.g. the fused block kernel with OFENet batch norm.
``execution.loop`` and ``replay.backend`` each have one legal value
(``"scan"``, ``"device"``): every run trains through the scanned superstep
over the device replay. ``to_dict``/``from_dict`` serialize the tree
(unknown keys are ignored with a ``SpecWarning`` — forward compat for older
binaries reading newer checkpoints), and ``override(**kwargs)`` builds sweep
variants from dotted paths (``{"network.num_units": 512}``) or the flat
aliases (``num_units=512``).

On top of the spec sits the resumable ``Experiment`` handle:

    exp = Experiment.from_spec(spec)        # builds the Trainer, no jit yet
    exp.run(10_000)                         # advance in scanned chunks
    exp.save("run.npz")                     # full state -> checkpoint/ckpt.py
    ...
    exp = Experiment.restore("run.npz")     # spec read back from metadata
    exp.run(10_000)                         # == uninterrupted 20k, seed-exact
    rows = list(exp.metrics())              # RunResult-style eval rows

``save`` round-trips the complete training state — agent/actors/replay
pytree (typed PRNG keys stored as raw key data) and the accumulated eval
history — through ``repro.checkpoint.ckpt`` with the spec serialized into
the checkpoint metadata, so a checkpoint is self-describing.

With ``obs.enabled`` the run additionally streams per-step training
diagnostics (``repro.obs``): the driver flushes each chunk's stacked scalar
stream to the configured sinks, and ``save`` drains the async writer next
to the effects barrier. Enabling obs changes training outputs bitwise not
at all (tests/test_obs.py).

Paper scenarios are named in ``repro.rl.presets``. Grids of spec variants
(a figure's sweep, a seed battery) can run as ONE vmapped device program
per compiled shape through ``repro.rl.sweep`` (``Sweep.from_grid`` /
``Fleet``) instead of a sequential loop of ``Experiment``s.
"""
from __future__ import annotations

import ast
import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.guard.monitor import GuardSpec, GuardViolation, Monitor
from repro.core.blocks import BLOCK_BACKENDS, CONNECTIVITIES
from repro.core.ofenet import OFENetConfig
from repro.common import ACTIVATIONS
from repro.obs.stream import ObsRun
from repro.obs.trace import annotate
from repro.obs.writers import SINKS
from repro.rl.envs import ENVS
from repro.rl.runner import RunResult, Trainer, TrainLoopState

ALGOS = ("sac", "td3")
REPLAY_BACKENDS = ("device",)
REPLAY_KERNELS = ("xla", "pallas")
LOOPS = ("scan",)
# why execution.loop / replay.backend accept one value each
_ONE_DRIVER = ("the scan driver over the device replay replaced the "
               "per-step python loop and the host NumPy replay")

_SPEC_VERSION = 1


class SpecError(ValueError):
    """Invalid spec field or unsupported combination, caught at construction."""


class SpecWarning(UserWarning):
    """Forward-compat key skipping: a newer writer's fields were ignored."""


def _choice(spec: str, field: str, value, choices, why: str = "") -> None:
    if value not in choices:
        raise SpecError(f"{spec}.{field}={value!r} is not one of "
                        f"{tuple(choices)}" + (f": {why}" if why else ""))


def _positive(spec: str, field: str, value, minimum: int = 1) -> None:
    if not isinstance(value, (int, np.integer)) or isinstance(value, bool) \
            or value < minimum:
        raise SpecError(f"{spec}.{field}={value!r} must be an int >= "
                        f"{minimum}")


def _boolean(spec: str, field: str, value) -> None:
    # a truthy string like "false" silently flipping a knob is exactly the
    # stringly-typed failure this spec tree exists to kill
    if not isinstance(value, (bool, np.bool_)):
        raise SpecError(f"{spec}.{field}={value!r} must be a bool")


def _sub_from_dict(cls, name: str, d: dict):
    if not isinstance(d, dict):
        raise SpecError(f"spec section {name!r} must be a dict, got "
                        f"{type(d).__name__}")
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(d) - known)
    if unknown:
        warnings.warn(f"ExperimentSpec.from_dict: ignoring unknown "
                      f"{name} keys {unknown} (forward compat)", SpecWarning,
                      stacklevel=3)
    try:
        return cls(**{k: v for k, v in d.items() if k in known})
    except SpecError:
        raise
    except ValueError as e:
        # sections defined outside this module (GuardSpec lives in
        # repro.guard so the guard package never imports repro.rl) raise
        # plain ValueError — normalize to SpecError for callers
        raise SpecError(str(e)) from e


# --------------------------------------------------------------- sub-specs

@dataclasses.dataclass(frozen=True)
class NetworkSpec:
    """Policy/value trunk: the paper's width/depth/connectivity axes."""
    num_units: int = 256
    num_layers: int = 2
    connectivity: str = "densenet"     # mlp | resnet | densenet | d2rl
    activation: str = "swish"
    block_backend: str = "jnp"         # jnp | fused (streaming stack kernel)

    def __post_init__(self):
        _positive("network", "num_units", self.num_units)
        _positive("network", "num_layers", self.num_layers, minimum=0)
        _choice("network", "connectivity", self.connectivity, CONNECTIVITIES)
        _choice("network", "activation", self.activation, sorted(ACTIVATIONS))
        _choice("network", "block_backend", self.block_backend,
                BLOCK_BACKENDS)


@dataclasses.dataclass(frozen=True)
class OFENetSpec:
    """Decoupled representation learning (paper §3.1)."""
    enabled: bool = True
    num_units: int = 64
    num_layers: int = 4
    connectivity: str = "densenet"
    activation: str = "swish"
    batch_norm: bool = False           # paper's OFENet uses BN; the RL
                                       # runner default keeps it off

    def __post_init__(self):
        _boolean("ofenet", "enabled", self.enabled)
        _boolean("ofenet", "batch_norm", self.batch_norm)
        _positive("ofenet", "num_units", self.num_units)
        _positive("ofenet", "num_layers", self.num_layers, minimum=0)
        _choice("ofenet", "connectivity", self.connectivity, CONNECTIVITIES)
        _choice("ofenet", "activation", self.activation, sorted(ACTIVATIONS))


@dataclasses.dataclass(frozen=True)
class ReplaySpec:
    """Device replay storage + sampling (``repro.replay``)."""
    backend: str = "device"            # the one backend (see _ONE_DRIVER)
    kernel: str = "xla"                # sum-tree impl: xla | pallas
    capacity: int = 100_000
    prioritized: bool = True
    n_step: int = 1                    # Ape-X n-step returns

    def __post_init__(self):
        _choice("replay", "backend", self.backend, REPLAY_BACKENDS,
                _ONE_DRIVER)
        _choice("replay", "kernel", self.kernel, REPLAY_KERNELS)
        _boolean("replay", "prioritized", self.prioritized)
        _positive("replay", "capacity", self.capacity)
        _positive("replay", "n_step", self.n_step)


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """How the training loop runs: sharding, batch, actor pool."""
    loop: str = "scan"                 # the one driver (see _ONE_DRIVER)
    mesh_shards: int = 0               # >0: actors+replay on a data mesh
    batch_size: int = 256
    total_steps: int = 2000            # default budget for run(steps=None)
    warmup_steps: int = 500
    distributed: bool = True           # Ape-X actor pool vs 1-step loop
    n_core: int = 2
    n_env: int = 32
    seed: int = 0

    def __post_init__(self):
        _choice("execution", "loop", self.loop, LOOPS, _ONE_DRIVER)
        _boolean("execution", "distributed", self.distributed)
        _positive("execution", "mesh_shards", self.mesh_shards, minimum=0)
        _positive("execution", "batch_size", self.batch_size)
        _positive("execution", "total_steps", self.total_steps, minimum=0)
        _positive("execution", "warmup_steps", self.warmup_steps, minimum=0)
        _positive("execution", "n_core", self.n_core)
        _positive("execution", "n_env", self.n_env)
        _positive("execution", "seed", self.seed, minimum=0)

    @property
    def n_actors(self) -> int:
        return self.n_core * self.n_env if self.distributed else 1


@dataclasses.dataclass(frozen=True)
class EvalSpec:
    """Evaluation cadence + effective-rank instrumentation."""
    every: int = 500
    episodes: int = 3
    srank_every: int = 0               # 0 = off

    def __post_init__(self):
        _positive("eval", "every", self.every)
        _positive("eval", "episodes", self.episodes)
        _positive("eval", "srank_every", self.srank_every, minimum=0)


@dataclasses.dataclass(frozen=True)
class ObsSpec:
    """In-loop telemetry (``repro.obs``): stream cadence, sinks, traces.

    Enabling obs never perturbs training: the scan body always emits its
    scalar metrics in full and downsampling happens on the host, so outputs
    are bitwise-identical with obs on or off, and resume stays bitwise with
    a sink attached. ``grad_norms`` adds per-network gradient/update-ratio
    taps to the algo update (pure consumers of existing gradients).
    ``trace=N`` captures a ``jax.profiler`` trace of the first N chunks
    into ``<log_dir>/trace/``."""
    enabled: bool = False
    log_every: int = 50                # absolute-step cadence of train rows
    sinks: Tuple[str, ...] = ("memory",)   # jsonl | csv | memory
    grad_norms: bool = True            # per-net grad/update-ratio metrics
    trace: int = 0                     # profile the first N chunks (0 = off)
    log_dir: str = ""                  # required by jsonl/csv/trace

    def __post_init__(self):
        _boolean("obs", "enabled", self.enabled)
        _boolean("obs", "grad_norms", self.grad_norms)
        _positive("obs", "log_every", self.log_every)
        _positive("obs", "trace", self.trace, minimum=0)
        sinks = self.sinks
        if isinstance(sinks, str):     # CLI: obs.sinks=jsonl or jsonl,csv
            sinks = tuple(s for s in sinks.split(",") if s)
        if not isinstance(sinks, (tuple, list)):
            raise SpecError(f"obs.sinks={self.sinks!r} must be a "
                            f"tuple/list of {SINKS}")
        object.__setattr__(self, "sinks", tuple(sinks))
        for s in self.sinks:
            _choice("obs", "sinks", s, SINKS)
        needs_dir = [s for s in self.sinks if s in ("jsonl", "csv")]
        if self.trace:
            needs_dir.append("trace")
        if needs_dir and not self.log_dir:
            raise SpecError(
                f"obs.log_dir is required by {sorted(set(needs_dir))}: "
                f"file sinks and profiler traces need a directory to "
                f"write into (obs.log_dir='runs/exp0').")


# flat field name -> dotted spec path, used by override()
_ALIASES: Dict[str, str] = {
    "num_units": "network.num_units",
    "num_layers": "network.num_layers",
    "connectivity": "network.connectivity",
    "activation": "network.activation",
    "block_backend": "network.block_backend",
    "use_ofenet": "ofenet.enabled",
    "ofenet_units": "ofenet.num_units",
    "ofenet_layers": "ofenet.num_layers",
    "replay_backend": "replay.backend",
    "replay_kernel": "replay.kernel",
    "replay_capacity": "replay.capacity",
    "prioritized": "replay.prioritized",
    "n_step": "replay.n_step",
    "loop": "execution.loop",
    "mesh_shards": "execution.mesh_shards",
    "batch_size": "execution.batch_size",
    "total_steps": "execution.total_steps",
    "warmup_steps": "execution.warmup_steps",
    "distributed": "execution.distributed",
    "n_core": "execution.n_core",
    "n_env": "execution.n_env",
    "seed": "execution.seed",
    "eval_every": "eval.every",
    "eval_episodes": "eval.episodes",
    "srank_every": "eval.srank_every",
    "log_every": "obs.log_every",
    "log_dir": "obs.log_dir",
}

_SECTIONS: Tuple[Tuple[str, type], ...] = (
    ("network", NetworkSpec), ("ofenet", OFENetSpec), ("replay", ReplaySpec),
    ("execution", ExecutionSpec), ("eval", EvalSpec), ("obs", ObsSpec),
    ("guard", GuardSpec))


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """The full, validated description of one training run."""
    env: str = "pendulum"
    algo: str = "sac"
    network: NetworkSpec = dataclasses.field(default_factory=NetworkSpec)
    ofenet: OFENetSpec = dataclasses.field(default_factory=OFENetSpec)
    replay: ReplaySpec = dataclasses.field(default_factory=ReplaySpec)
    execution: ExecutionSpec = dataclasses.field(
        default_factory=ExecutionSpec)
    eval: EvalSpec = dataclasses.field(default_factory=EvalSpec)
    obs: ObsSpec = dataclasses.field(default_factory=ObsSpec)
    guard: GuardSpec = dataclasses.field(default_factory=GuardSpec)

    # ------------------------------------------------------- validation
    def __post_init__(self):
        _choice("spec", "env", self.env, sorted(ENVS))
        _choice("spec", "algo", self.algo, ALGOS)
        for name, cls in _SECTIONS:
            if not isinstance(getattr(self, name), cls):
                raise SpecError(f"spec.{name} must be a {cls.__name__}, got "
                                f"{type(getattr(self, name)).__name__}")
        self._validate_combos()

    def _validate_combos(self):
        r, x = self.replay, self.execution
        if x.mesh_shards > 0:
            for fname, val in (("n_actors", x.n_actors),
                               ("batch_size", x.batch_size),
                               ("capacity", r.capacity)):
                if val % x.mesh_shards:
                    raise SpecError(
                        f"execution.mesh_shards={x.mesh_shards} must divide "
                        f"{fname}={val} (actors, batch and replay rows are "
                        f"split evenly across the mesh 'data' axis)")
        if (self.guard.enabled and self.guard.srank_collapse > 0
                and not self.eval.srank_every):
            raise SpecError(
                "guard.srank_collapse>0 requires eval.srank_every>0: the "
                "collapse guard watches the effective-rank series, which "
                "is only measured when srank instrumentation is on.")
        if (self.network.block_backend == "fused" and self.ofenet.enabled
                and self.ofenet.batch_norm):
            raise SpecError(
                "network.block_backend='fused' does not support "
                "ofenet.batch_norm=True: the streaming stack kernel has no "
                "fused BN pass yet (ROADMAP follow-on), and silently "
                "falling back would train a different program than "
                "requested. Set ofenet.batch_norm=False or "
                "network.block_backend='jnp'.")

    # ---------------------------------------------------- serialization
    def to_dict(self) -> dict:
        d = {"version": _SPEC_VERSION, "env": self.env, "algo": self.algo}
        for name, _ in _SECTIONS:
            d[name] = dataclasses.asdict(getattr(self, name))
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentSpec":
        """Rebuild a spec from ``to_dict`` output (e.g. checkpoint
        metadata). Unknown keys — a newer writer's fields — are skipped
        with a ``SpecWarning`` instead of failing, so old code can still
        load new checkpoints; values it does understand are validated as
        usual."""
        d = dict(d)
        d.pop("version", None)
        kw: Dict[str, Any] = {}
        for f in ("env", "algo"):
            if f in d:
                kw[f] = d.pop(f)
        for name, sub in _SECTIONS:
            if name in d:
                kw[name] = _sub_from_dict(sub, name, d.pop(name))
        if d:
            warnings.warn(f"ExperimentSpec.from_dict: ignoring unknown "
                          f"keys {sorted(d)} (forward compat)", SpecWarning,
                          stacklevel=2)
        return cls(**kw)

    def override(self, **kwargs) -> "ExperimentSpec":
        """A new validated spec with the given fields replaced.

        Keys are dotted spec paths (``{"replay.kernel": "pallas"}`` via
        ``override(**mapping)``) or the flat aliases (``num_units=512``,
        ``replay_kernel="pallas"``); top-level
        ``env``/``algo`` work as-is. Unknown keys raise ``SpecError`` —
        sweeps should fail loudly, not drop a knob."""
        d = self.to_dict()
        for key, value in kwargs.items():
            path = _ALIASES.get(key, key)
            parts = path.split(".")
            node = d
            ok = True
            for p in parts[:-1]:
                if not isinstance(node.get(p), dict):
                    ok = False
                    break
                node = node[p]
            if not ok or parts[-1] not in node or parts[-1] == "version" \
                    or isinstance(node[parts[-1]], dict):
                raise SpecError(
                    f"unknown override key {key!r}; use a dotted spec path "
                    f"(e.g. 'network.num_units'), a flat alias "
                    f"({sorted(_ALIASES)}), or 'env'/'algo'")
            node[parts[-1]] = value
        # d round-trips through from_dict (no unknown keys possible)
        return ExperimentSpec.from_dict(d)

    def ofenet_config(self, obs_dim: int, act_dim: int) -> OFENetConfig:
        o = self.ofenet
        return OFENetConfig(
            state_dim=obs_dim, action_dim=act_dim, num_layers=o.num_layers,
            num_units=o.num_units, connectivity=o.connectivity,
            activation=o.activation, batch_norm=o.batch_norm,
            block_backend=self.network.block_backend)


def parse_overrides(pairs: List[str]) -> Dict[str, Any]:
    """CLI ``--override key=value`` pairs -> an ``override()`` kwargs dict.

    Values parse as Python literals when possible (``True``, ``3``,
    ``0.5``), with shell-style ``true``/``false`` accepted as bools, and
    fall back to strings (``pallas``, ``relu``) — bool-typed spec fields
    reject leftover strings at validation, so a typo'd flag can never run
    the wrong experiment silently."""
    out: Dict[str, Any] = {}
    for s in pairs:
        key, sep, val = s.partition("=")
        if not sep or not key:
            raise SpecError(f"override {s!r} must be key=value "
                            f"(e.g. replay.kernel=pallas)")
        if val.lower() in ("true", "false"):
            out[key] = val.lower() == "true"
            continue
        try:
            out[key] = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            out[key] = val
    return out


# ------------------------------------------------------------------ handle

def _is_key(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype,
                                                  jax.dtypes.prng_key)


def _unkey(tree):
    """Typed PRNG key leaves -> raw uint32 key data (npz-serializable)."""
    return jax.tree_util.tree_map(
        lambda x: jax.random.key_data(x) if _is_key(x) else x, tree)


def _rekey(tree, template):
    """Inverse of ``_unkey`` using ``template``'s leaves to find keys."""
    return jax.tree_util.tree_map(
        lambda saved, tmpl: (jax.random.wrap_key_data(jnp.asarray(saved))
                             if _is_key(tmpl) else saved),
        tree, template)


class Experiment:
    """A resumable handle on one training run.

    ``from_spec`` builds the Trainer (env, agent ops, replay wiring) without
    executing any jitted program; the first ``run``/``save`` initializes
    state (agent init + random-policy warmup). ``run(steps)`` advances in
    scanned chunks (``Trainer.chunk_fn``), evaluating at absolute multiples of
    ``spec.eval.every`` — so ``run(N); save; restore; run(M)`` is seed-exact
    with an uninterrupted ``run(N + M)``. ``save``/``restore`` round-trip
    the complete training state through ``repro.checkpoint.ckpt`` with the
    spec in the checkpoint metadata.

    Bitwise-reproducibility contract: ``run(N); save; restore; run(M)`` is
    bitwise-equal (eval returns, final params, replay state) to an
    uninterrupted ``run(N + M)`` at ANY split point. A chunk is one
    ``lax.scan`` over all its supersteps with the last step's metrics/batch
    carried through the scan carry (``Trainer.chunk_fn``) — the superstep
    only ever compiles as the scan body, so re-chunking the same step
    sequence executes the identical compiled computation per step.
    """

    def __init__(self, spec: ExperimentSpec, *, mesh=None):
        self.spec = spec
        self.trainer = Trainer(spec, mesh=mesh)
        self._obs = ObsRun(spec.obs)
        self._monitor = Monitor(spec.guard) if spec.guard.enabled else None
        self._guard_store = None       # DurableStore via attach_guard()
        self._ls: Optional[TrainLoopState] = None
        self.step = 0
        self.returns: List[float] = []
        self.eval_steps: List[int] = []
        self.sranks: List[int] = []
        self._rows: List[Dict[str, float]] = []
        self._last_metrics: Dict[str, float] = {}
        self._last_batch = None
        self._last_priorities = None
        self._wall = 0.0

    # ------------------------------------------------------- constructors
    @classmethod
    def from_spec(cls, spec: ExperimentSpec, *, mesh=None) -> "Experiment":
        return cls(spec, mesh=mesh)

    @classmethod
    def restore(cls, path: str, *, mesh=None) -> "Experiment":
        """Rebuild a handle from ``save`` output: spec from the checkpoint
        metadata, every state leaf from the array payload."""
        meta = ckpt.load_metadata(path)
        if meta is None or "spec" not in meta:
            raise FileNotFoundError(
                f"{path}: no spec-bearing checkpoint metadata "
                f"({path}.meta.json) — was this saved by Experiment.save?")
        spec = ExperimentSpec.from_dict(meta["spec"])
        exp = cls(spec, mesh=mesh)
        exp._load_payload(path, meta)
        exp._obs.log_event("restore", step=exp.step, path=str(path))
        exp._obs.drain()
        return exp

    def _load_payload(self, path: str, meta: dict) -> None:
        """Load a ``save`` checkpoint's state INTO this handle, replacing
        whatever it holds (``restore``'s workhorse; also the in-place
        rollback path of guard policy='rollback', which reuses the live
        handle's compiled programs instead of rebuilding a Trainer)."""
        template = self.trainer.init_template()
        tree = ckpt.restore(path, {"loop": _unkey(template)})
        self._ls = self.trainer._pin(_rekey(tree["loop"], template),
                                     put=True)

        st = meta["experiment"]
        self.step = int(st["step"])
        self.returns = [float(r) for r in st["returns"]]
        self.eval_steps = [int(s) for s in st["eval_steps"]]
        self.sranks = [int(s) for s in st["sranks"]]
        self._rows = [dict(r) for r in st.get("rows", [])]
        self._last_metrics = dict(st.get("last_metrics", {}))
        self._wall = float(st.get("wall_time_s", 0.0))
        self.trainer.n_params = int(st["n_params"])
        # dispatch accounting continues across the resume so
        # metrics["host_dispatches"] matches an uninterrupted run
        self.trainer.dispatches = int(st.get("dispatches", 0))
        self._obs.load_state(st.get("obs"))

    # ------------------------------------------------------------ running
    def _ensure_init(self):
        if self._ls is None:
            self._ls = self.trainer.init()

    def run(self, steps: Optional[int] = None, *,
            progress: Optional[Callable] = None, eval_at_end: bool = False,
            keep_last: bool = False) -> RunResult:
        """Advance ``steps`` gradient steps (default: the spec budget).

        Evaluation/srank fire at absolute multiples of ``spec.eval.every`` /
        ``srank_every``, independent of where ``run`` calls start and stop —
        that is what makes interrupted and uninterrupted schedules
        seed-exact. ``eval_at_end`` additionally evaluates at the final step
        of THIS call (it consumes a PRNG split, so it is only
        bitwise-reproducible by runs stopping at the same step).
        ``keep_last`` retains the final sampled batch + priorities
        (loss-landscape tooling). Returns the cumulative ``RunResult``
        snapshot.

        With ``spec.obs.enabled`` the call also streams diagnostics: each
        chunk's stacked scalar stream + a per-chunk timing event land in the
        sinks via the async writer, which is drained before returning."""
        t0 = time.time()
        x, ev, obs = self.spec.execution, self.spec.eval, self._obs
        eval_every, srank_every = ev.every, ev.srank_every
        if steps is None:
            steps = x.total_steps
        self._ensure_init()
        trainer, ls = self.trainer, self._ls
        start, end = self.step, self.step + steps

        # chunks stop at every eval point AND (when instrumented) every
        # srank point. Chunking is pure scheduling: the superstep only ever
        # compiles as the scan body, so any chunking of the same step
        # sequence is bitwise-identical (Trainer.chunk_fn).
        step = start
        mon = self._monitor
        while step < end:
            stops = [(step // eval_every + 1) * eval_every, end]
            if srank_every:
                stops.append((step // srank_every + 1) * srank_every)
            stop = min(stops)
            do_eval = (stop % eval_every == 0
                       or (eval_at_end and stop == end))
            do_srank = bool(srank_every) and stop % srank_every == 0
            want_last = keep_last and stop == end
            snap = (self._guard_snapshot(ls, step)
                    if mon is not None else None)
            obs.trace.begin()
            tc = time.time()
            with annotate("repro.chunk_dispatch"):
                ls, out = trainer.chunk_fn(stop - step, do_eval,
                                           do_srank)(ls)
            hstream = None
            if "stream" in out:
                with annotate("repro.obs.flush"):
                    hstream = jax.device_get(out["stream"])
            if mon is not None:
                with annotate("repro.guard.check"):
                    viol = mon.check_stream(step, hstream) \
                        if hstream is not None else []
                    viol += mon.check_params(stop, ls.agent["params"])
                if viol:
                    obs.trace.end()
                    ls, step = self._guard_recover(viol, snap)
                    continue
            if hstream is not None:
                with annotate("repro.obs.flush"):
                    obs.flush_chunk(step, hstream)
                obs.chunk_event(step, stop, time.time() - tc)
            obs.trace.end()
            step = stop
            if do_srank:
                # explicit device_get: the chunk epilogue is the ONE
                # sanctioned host<->device barrier in the driver, so
                # the steady state stays clean under
                # jax.transfer_guard("disallow") (repro.check dynamic)
                srank = int(jax.device_get(out["srank"]))
                self.sranks.append(srank)
                obs.log_event("srank", step=step, srank=srank)
                if mon is not None:
                    with annotate("repro.guard.check"):
                        viol = mon.check_srank(step, self.sranks)
                    if viol:
                        ls, step = self._guard_recover(viol, snap)
                        continue
            if want_last:
                self._last_batch, self._last_priorities = out["last"]
            if do_eval:
                with annotate("repro.eval_readback"):
                    ev_ret, scal = jax.device_get((out["eval"],
                                                   out["scal"]))
                self._record_eval(
                    step, float(np.mean(ev_ret)),
                    {k: float(v) for k, v in scal.items()}, progress)
        self._ls, self.step = ls, end
        wall = time.time() - t0
        self._wall += wall
        if obs.enabled:
            obs.log_event(
                "run", step=end, steps=steps, wall_s=wall,
                steps_per_sec=steps / wall if wall > 0 else 0.0,
                host_dispatches=trainer.dispatches,
                chunk_compiles=len(trainer._chunks))
            if obs.trace.n_chunks:
                obs.log_event("trace", step=end, status=obs.trace.status,
                              dir=obs.trace.trace_dir)
            obs.drain()
        return self.result(include_state=keep_last)

    def _record_eval(self, step, ret, scalars, progress):
        self.returns.append(ret)
        self.eval_steps.append(step)
        self._last_metrics = scalars
        self._rows.append({"step": step, "return": ret, **scalars})
        self._obs.log_eval(step, ret, scalars)
        if progress:
            progress(step, ret, scalars)

    # ------------------------------------------------------------- guarding
    def attach_guard(self, store) -> None:
        """Attach a ``repro.guard.store.DurableStore``: the checkpoint
        source for guard policy='rollback' (the supervisor attaches the
        same store it saves into)."""
        self._guard_store = store

    def _guard_snapshot(self, ls: TrainLoopState, step: int) -> dict:
        """Pre-segment snapshot for the skip policy. Device state is free —
        JAX arrays are immutable, holding the old ``ls`` reference IS the
        snapshot — so only the host-side pieces are recorded: history list
        lengths and the obs cursor."""
        return {"ls": ls, "step": step, "obs": self._obs.state(),
                "hist": (len(self.returns), len(self.eval_steps),
                         len(self.sranks), len(self._rows))}

    def _guard_recover(self, violations, snap) -> Tuple[TrainLoopState, int]:
        """Apply ``guard.policy`` to a non-empty violation list; returns the
        (state, step) the driver loop should continue from. Raises
        ``GuardViolation`` for halt, a spent recovery budget, or an
        impossible rollback."""
        mon, obs = self._monitor, self._obs
        for v in violations:
            obs.log_event("guard_violation", **v.as_dict())
        try:
            if mon.spec.policy == "halt":
                raise GuardViolation(
                    f"guard: halt on {violations[0].reason} at step "
                    f"{violations[0].step}", violations, mon.recoveries)
            ordinal = mon.spend_recovery(violations)
            if mon.spec.policy == "skip":
                ls, step = self._guard_skip(snap, ordinal)
            else:
                ls, step = self._guard_rollback(violations, ordinal)
        except GuardViolation:
            obs.drain()
            raise
        obs.log_event("guard_" + mon.spec.policy, step=step,
                      recovery=ordinal, detected=violations[0].step,
                      reason=violations[0].reason)
        obs.drain()
        return ls, step

    def _guard_skip(self, snap, ordinal) -> Tuple[TrainLoopState, int]:
        """Discard the offending segment: rewind to the pre-segment
        snapshot and fold the recovery ordinal into the PRNG key, so the
        re-run explores a perturbed trajectory instead of replaying the
        same divergence."""
        r0, e0, s0, w0 = snap["hist"]
        del self.returns[r0:], self.eval_steps[e0:]
        del self.sranks[s0:], self._rows[w0:]
        self._obs.load_state(snap["obs"])
        ls = snap["ls"]
        ls = ls._replace(key=jax.random.fold_in(ls.key, ordinal))
        self._ls = ls
        return ls, snap["step"]

    def _guard_rollback(self, violations, ordinal) \
            -> Tuple[TrainLoopState, int]:
        """Restore the newest GOOD checkpoint from the attached
        ``DurableStore`` (falling back past corrupt ones) and perturb the
        key with the recovery ordinal."""
        store, mon = self._guard_store, self._monitor
        if store is None:
            raise GuardViolation(
                "guard.policy='rollback' needs a DurableStore — call "
                "Experiment.attach_guard(store) (the supervisor does this "
                "automatically)", violations, mon.recoveries)
        path = store.restore_latest(
            on_bad=lambda bad: self._obs.log_event(
                "guard_bad_checkpoint", step=self.step,
                path=str(bad.path), reason=bad.reason))
        if path is None:
            raise GuardViolation(
                f"guard rollback: no good checkpoint in {store.dir}",
                violations, mon.recoveries)
        payload = store.payload(path)
        self._load_payload(payload, ckpt.load_metadata(payload))
        ls = self._ls._replace(
            key=jax.random.fold_in(self._ls.key, ordinal))
        self._ls = ls
        return ls, self.step

    # ------------------------------------------------------------ results
    def metrics(self) -> Iterator[Dict[str, float]]:
        """Stream the RunResult-style eval rows recorded so far (one dict
        per eval point: step, return, and the scalar training metrics)."""
        return iter([dict(r) for r in self._rows])

    @property
    def obs(self) -> ObsRun:
        """The observability engine: sinks (``obs.rows`` for the memory
        sink), stream counters, and the profiler-trace status."""
        return self._obs

    def close(self) -> None:
        """Stop a still-active profiler capture and close the obs sinks."""
        self._obs.close()

    def policy(self) -> "Policy":
        """The run's current inference handle (``repro.rl.Policy``) —
        deterministic eval/serving actions via ``act_deterministic``,
        stochastic collection actions via ``act``. Initializes the run
        state on first use; shares the Trainer's compile cache."""
        from repro.rl.policy import Policy
        return Policy.from_experiment(self)

    def result(self, *, include_state: bool = False) -> RunResult:
        """The cumulative RunResult snapshot."""
        metrics_out = dict(self._last_metrics,
                           host_dispatches=float(self.trainer.dispatches))
        return RunResult(
            returns=list(self.returns), eval_steps=list(self.eval_steps),
            sranks=list(self.sranks), metrics=metrics_out,
            param_count=getattr(self.trainer, "n_params", 0),
            wall_time_s=self._wall,
            state=(self._ls.agent if include_state and self._ls is not None
                   else None),
            last_batch=self._last_batch,
            last_priorities=(None if self._last_priorities is None
                             else np.asarray(self._last_priorities)))

    # ------------------------------------------------------- checkpointing
    def save(self, path: str) -> None:
        """Write the full training state + spec metadata to ``path``.

        Layout: one npz holding the ``TrainLoopState`` pytree (typed PRNG
        keys as raw key data); a sibling ``.meta.json`` with the serialized
        spec and eval history."""
        self._ensure_init()
        # A mid-period stop can leave the last chunk still executing (its
        # outputs were never fetched). Drain the program AND any ordered
        # effects first; the obs writer queue drains at the same barrier so
        # the metric files are consistent with the snapshot.
        jax.block_until_ready(self._ls)
        jax.effects_barrier()
        self._obs.drain()
        state: Dict[str, Any] = {
            "step": self.step, "returns": self.returns,
            "eval_steps": self.eval_steps, "sranks": self.sranks,
            "rows": self._rows, "last_metrics": self._last_metrics,
            "wall_time_s": self._wall,
            "n_params": int(self.trainer.n_params),
            "dispatches": int(self.trainer.dispatches),
            "obs": self._obs.state(),
        }
        ckpt.save(path, {"loop": _unkey(self._ls)},
                  metadata={"spec": self.spec.to_dict(), "experiment": state})
        self._obs.log_event("save", step=self.step, path=str(path))
        self._obs.drain()
