"""repro.rl — the paper's RL system behind a layered, typed experiment API.

Quick tour
----------
The run surface is a validated spec tree plus a resumable handle
(``repro.rl.experiment``)::

    from repro.rl import Experiment, presets

    spec = presets.get("quickstart").override(num_units=256)
    exp = Experiment.from_spec(spec)       # builds everything, no jit yet
    exp.run(5_000)                         # advance; eval at spec cadence
    exp.save("run.npz")                    # full state + spec metadata
    exp = Experiment.restore("run.npz")    # later / elsewhere
    exp.run(5_000)                         # BITWISE-equal to run(10_000)
    rows = list(exp.metrics())             # per-eval metric rows

Save/restore is bitwise-reproducible at ANY step, not just at eval-chunk
boundaries: interrupted and uninterrupted schedules produce identical eval
returns, final params and replay state (a chunk is one ``lax.scan`` with
the last step's outputs carried through the scan carry, so the superstep
compiles identically however the run is chunked — see ``Experiment`` /
``Trainer.chunk_fn``). Every run trains through that scanned superstep
over the device replay (``repro.replay``).

Spec tree (``ExperimentSpec``): ``env``/``algo`` plus six sub-specs —
``network`` (width/depth/connectivity/activation/``block_backend``),
``ofenet`` (decoupled representation), ``replay`` (xla|pallas sum-tree
kernel, capacity, PER, n-step), ``execution`` (mesh shards, batch, steps,
Ape-X actor pool, seed), ``eval`` (cadence, episodes, srank) and ``obs``
(telemetry, below). Invalid values and unsupported combinations (e.g. the
fused block kernel with OFENet batch norm) raise ``SpecError`` at
construction. Specs serialize via ``to_dict``/``from_dict`` (unknown keys
skipped with a ``SpecWarning`` — forward compat) and sweep via
``override`` with dotted paths or the flat aliases.

Observability (``repro.obs``, configured by ``ObsSpec``)::

    spec = spec.override(**{"obs.enabled": True,
                            "obs.sinks": ("jsonl",),
                            "obs.log_dir": "runs/exp0",
                            "obs.log_every": 50})
    Experiment.from_spec(spec).run()
    # then: python -m repro.obs.report runs/exp0

The driver streams every per-step scalar training metric out of the
jitted chunk as stacked scan outputs and flushes them in the chunk
epilogue. Rows flow through an async buffered writer
into the configured sinks (``jsonl`` / ``csv`` / ``memory``).
``obs.grad_norms`` adds per-network gradient-norm + update-ratio taps;
``obs.trace=N`` captures a ``jax.profiler`` trace of the first N chunks.
Enabling obs changes training outputs bitwise NOT AT ALL, and the
save/restore contract above holds with sinks attached — the stream is
always emitted inside the scan and downsampled on the host against absolute
steps, so obs knobs never touch the compiled body (tests/test_obs.py).
``python -m repro.obs.report <log_dir>`` summarizes throughput, grad-norm /
staleness trajectories and instability events from the stream.

Sweeps (``repro.rl.sweep``): run a whole paper figure as ONE device
program. A ``Fleet`` stacks N members' training states along a leading
member axis and advances all of them through one jitted ``lax.scan``
chunk whose body is ``jax.vmap`` of the Trainer superstep;
``Sweep.from_grid`` expands a preset x ``axis`` overrides x ``seeds``
grid and partitions it into per-compiled-shape sub-fleets (reported via
``Sweep.partition``)::

    from repro.rl import Sweep

    sweep = Sweep.from_grid("fig3-width",
                            axis={"num_units": [64, 256]}, seeds=5)
    sweep.run()                          # 2 compiled programs, 10 members
    best = max(sweep.results(), key=lambda m: m.result.max_return)

Fleets evaluate per member at the same absolute steps as solo
runs, support per-member early stopping (``stop_at_return`` /
``set_done`` freeze a member's carry without perturbing neighbors or
recompiling), checkpoint through the same ``ckpt.py`` path
(``Fleet.save``/``restore`` — fleet resume is bitwise at any split), give
each member its own obs stream (``<log_dir>/<member>/`` subdirs, rows
tagged ``member``), and offer PBT-style ``exploit_explore()`` between
chunks. Member-vs-solo parity is allclose (documented
``sweep.SOLO_PARITY_RTOL/ATOL``), not bitwise: vmap batches members'
matmuls together. Throughput: ``benchmarks/sweep_fleet.py``.

Fault tolerance (``repro.guard``, configured by the ``guard`` spec
section): long runs survive divergence, corrupt checkpoints and crashes.
``guard.enabled=True`` turns on in-loop health monitoring — non-finite
metric streams and params (detected at the exact offending step; the
stream is already emitted bitwise-invisibly, see obs above), loss spikes
vs a rolling median (``guard.spike_factor``) and srank collapse
(``guard.srank_collapse``) — with three recovery policies:

* ``halt`` (default): raise ``GuardViolation`` carrying every detection.
* ``skip`` (solo only): rewind to the pre-segment state, perturb the RNG
  key with ``fold_in(key, ordinal)`` and re-run the segment.
* ``rollback``: restore the newest GOOD durable checkpoint from an
  attached ``DurableStore`` (``exp.attach_guard(store)``) and continue
  with the perturbed key. In a ``Fleet`` only the diverged member is
  rolled back — neighbors are bitwise undisturbed.

Recoveries are deterministic: a recovered trajectory equals
restore + ``fold_in(key, ordinal)`` + rerun, bit for bit, and the budget
(``guard.max_recoveries``) bounds how many a run may spend. Durable
checkpoints are staged, sha256-manifested and committed with a single
rename (``repro.guard.store``) so a crash mid-save can never destroy the
previous good one. For unattended runs,
``python -m repro.guard.supervise <preset> --dir runs/x`` wraps a run in
a crash-safe supervisor: segments with periodic durable saves, auto-resume
after SIGKILL/OOM (bitwise-equal to the uninterrupted run), bounded
retries with exponential backoff, and a structured ``incident.json`` when
the budget is spent. Every recovery path is exercised by deterministic
fault injection (``repro.guard.chaos``, the supervisor's ``--chaos``
flag, tests/test_guard.py).

Correctness tooling (``repro.check``): the determinism contract above —
no host impurity inside traced code, no PRNG key reuse, no hidden
host<->device syncs in the superstep, one compiled program per chunk
signature — is enforced by a two-part gate. The static half::

    python -m repro.check lint src

runs JAX-aware AST rules: **R001** host-impure calls (``time.time``,
``np.random.*``, ``uuid`` ...) reachable from jitted/scanned/vmapped
functions (their value bakes into the compiled program at trace time);
**R002** a PRNG key consumed by two ``jax.random.*`` calls without an
intervening ``split``/``fold_in`` rebind (correlated randomness); **R003**
Python ``if``/``while``/``assert`` on tracer values in traced scopes
(trace-time crash or hidden sync); **R004** ``.item()`` / ``float()`` /
``np.asarray`` on device values inside loop-body modules — fetch at the
chunk epilogue with explicit ``jax.device_get`` instead; **R005** modules
unreachable from any entrypoint; **R006** ``*Spec`` dataclass fields not
covered by ``validate``/``__post_init__``. Findings are diffed against the
checked-in ``check_baseline.json`` (every grandfathered entry needs a
``reason``), so CI fails only on NEW findings; a justified exception is
silenced inline with ``# check: disable=R00x -- why this is safe`` (the
reason is mandatory — omitting it is itself a finding). The dynamic half::

    python -m repro.check dynamic --preset smoke

executes a tiny run, then replays the same schedule under
``jax.transfer_guard("disallow")`` (any implicit transfer in the steady
state raises — D001), asserts the compile cache exactly matches the chunk
signatures the scheduler predicts, with zero recompiles on the second pass
(D002), and re-traces one superstep under ``checkify`` NaN/OOB checks
(D003). Both halves run in CI; rules and fixtures live in
``tests/test_check.py``.

Serving (``repro.rl.policy`` + ``repro.launch.serve_policy``): ONE
inference surface turns params into actions everywhere — collection,
eval and live traffic::

    from repro.rl import Policy

    pol = Policy.from_checkpoint("run.npz")   # agent/params subtree only
    a = pol.act_deterministic(obs)            # single obs or batch
    pol = exp.policy()                        # or: from a live Experiment

``Policy`` binds the algorithm's pure act/det functions (SAC
tanh-Gaussian sample / mean action, TD3 policy (+ exploration noise)) to
concrete params, batches single observations, and jit-caches per
(batch_shape, dtype) in a core SHARED across ``with_params`` rebinds —
swapping parameters never recompiles, which is the hot-swap contract the
serving engine builds on. ``envs.eval_returns`` consumes a ``Policy``
directly, so eval is just another policy client and is bitwise-identical
to the pre-refactor inlined closures (tests/test_policy.py pins the
matrix). For live traffic::

    python -m repro.launch.serve_policy smoke --ckpt-dir runs/x/ckpts

runs the continuous-batching server: a bounded request queue, a batcher
that coalesces up to ``max_batch`` requests (or ``max_wait_ms``) into
fixed padded batch slots (compile cache pinned to the slot set, like the
trainer's chunk signatures), ONE jitted ``act_deterministic`` per tick,
and a response demux. A watcher thread polls a ``repro.guard``
``DurableStore`` for new VERIFIED checkpoints and double-buffers the
param swap — restore into a shadow buffer, flip a generation pointer
between ticks — so a live learner (or ``repro.guard.supervise``) pushes
checkpoints without pausing serving and no response ever mixes param
generations. Throughput/latency: ``benchmarks/serve_policy.py``.

Presets (``repro.rl.presets``): every paper scenario by name —
``fig1-depth``, ``fig3-width``, ``fig4-grid``, ``fig5-connectivity``,
``fig6-ofenet``, ``fig8-distributed``, ``fig10-ablation``,
``fig13-activation``, ``table1-ours``, ``table1-orig`` — plus
``quickstart``, ``rl-distributed`` and ``smoke``. All ``benchmarks/fig*.py``
and ``examples/`` build through ``presets.get(name).override(...)``.
"""
from repro.rl.envs import ENVS, EnvSpec, eval_returns, make_env, \
    rollout_return
from repro.rl.policy import Policy
from repro.rl.runner import RunResult
from repro.rl.experiment import (EvalSpec, ExecutionSpec, Experiment,
                                 ExperimentSpec, NetworkSpec, ObsSpec,
                                 OFENetSpec, ReplaySpec, SpecError,
                                 SpecWarning, parse_overrides)
from repro.rl.sweep import Fleet, MemberResult, Sweep
from repro.rl import presets
from repro.guard import (DurableStore, GuardSpec, GuardViolation, Monitor,
                         Violation)
