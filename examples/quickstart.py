"""Quickstart: the paper's full technique on a small task, end to end.

Trains a SAC agent with the three-fold method — (1) OFENet decoupled
representation, (2) wide MLP-DenseNet policy/value nets, (3) Ape-X-style
distributed collection — on the pure-JAX pendulum swing-up, and prints the
effective-rank trace showing the rank-collapse mitigation (paper §4).

Built on the layered experiment API: the ``quickstart`` preset plus
``--override key=value`` tweaks (dotted spec paths or legacy flat aliases),
with optional checkpoint/resume through the run handle.

    PYTHONPATH=src python examples/quickstart.py [--steps 2000]
        [--override network.num_units=256] [--override replay.n_step=3]
        [--ckpt run.npz] [--resume run.npz]

Diagnosing instability: pass ``--log-dir runs/a`` to stream per-step
telemetry (losses, grad norms, update ratios) into ``runs/a/metrics.jsonl``
without changing a single trained bit, then summarize with

    PYTHONPATH=src python -m repro.obs.report runs/a

The report flags loss spikes (>10x the run median), non-finite values and
srank collapse. Add ``--trace 2`` to also capture a jax.profiler trace of
the first two chunk dispatches under ``<log-dir>/trace`` for TensorBoard.

Guarding a run: ``--guard halt`` turns on in-loop health checks (non-finite
streams/params, spikes, srank collapse) that stop the run at the exact
offending step with a ``GuardViolation`` listing every detection;
``--guard skip`` instead rewinds the current segment and re-runs it with a
``fold_in``-perturbed RNG key (bounded by ``guard.max_recoveries``). For
unattended training — durable checkpoints, rollback recovery, auto-resume
after a crash, and a structured ``incident.json`` — run under the
supervisor instead:

    PYTHONPATH=src python -m repro.guard.supervise quickstart \\
        --dir runs/q --retries 3

which survives SIGKILL/OOM bitwise (see ``repro.guard``).

Serving the trained policy: pass ``--serve`` to finish the run with an
in-process round trip through the continuous-batching inference engine —
the trained params are wrapped in a ``Policy`` handle, a ``PolicyServer``
coalesces concurrent requests into one jitted forward per tick, and the
demuxed actions are checked against a direct ``act_deterministic`` call.
The standalone server (with live checkpoint hot-swap from a durable
checkpoint directory) is

    PYTHONPATH=src python -m repro.launch.serve_policy quickstart \\
        --ckpt-dir runs/q/ckpts

Hacking on the loop itself? The determinism contract (no host impurity in
traced code, no key reuse, no hidden syncs, one program per chunk
signature) is gated by ``repro.check``:

    PYTHONPATH=src python -m repro.check lint src
    PYTHONPATH=src python -m repro.check dynamic --preset smoke
"""
import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.rl import Experiment, parse_overrides, presets


def serve_round_trip(exp, n_clients=4, per_client=8):
    """Serve the trained policy in-process: concurrent clients round-trip
    through the continuous-batching engine, answers checked against a
    direct ``Policy.act_deterministic`` call."""
    import threading

    import numpy as np

    from repro.launch.serve_policy import PolicyServer, ServeConfig

    pol = exp.policy()
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((n_clients, per_client,
                               pol.obs_dim)).astype(np.float32)
    got = np.zeros((n_clients, per_client, pol.act_dim), np.float32)
    with PolicyServer(pol, ServeConfig(max_batch=8)) as server:
        def client(c):
            for i in range(per_client):
                got[c, i] = server.submit(obs[c, i])

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = dict(server.stats)
    direct = np.asarray(pol.act_deterministic(obs.reshape(-1, pol.obs_dim)))
    ok = np.allclose(got.reshape(-1, pol.act_dim), direct,
                     rtol=1e-5, atol=1e-6)
    print(f"served {stats['requests']} requests in {stats['ticks']} batched "
          f"ticks (sizes {dict(sorted(stats['batch_hist'].items()))}) — "
          f"{'match' if ok else 'MISMATCH vs'} direct policy call")
    print("standalone server: python -m repro.launch.serve_policy "
          "<preset> --ckpt-dir <dir>")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--units", type=int, default=None,
                    help="network width (default 128; fresh runs only)")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="spec override, e.g. network.num_layers=4 or "
                         "replay_kernel=pallas (repeatable)")
    ap.add_argument("--ckpt", default="", help="save the run handle here")
    ap.add_argument("--resume", default="",
                    help="restore a --ckpt checkpoint and keep training")
    ap.add_argument("--log-dir", default="",
                    help="stream telemetry to <dir>/metrics.jsonl "
                         "(summarize: python -m repro.obs.report <dir>)")
    ap.add_argument("--trace", type=int, default=0, metavar="N",
                    help="profile the first N chunk dispatches "
                         "into <log-dir>/trace (needs --log-dir)")
    ap.add_argument("--serve", action="store_true",
                    help="after training, serve the policy in-process and "
                         "round-trip concurrent requests through the "
                         "continuous-batching engine")
    ap.add_argument("--guard", default="", choices=["", "halt", "skip"],
                    help="health guards: halt on divergence, or skip the "
                         "bad segment with a perturbed key (crash-safe "
                         "rollback: python -m repro.guard.supervise)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.resume:
        if args.override or args.units is not None or args.guard:
            ap.error("--override/--units/--guard cannot be combined with "
                     "--resume: the spec comes from the checkpoint metadata")
        exp = Experiment.restore(args.resume)
        print(f"resumed at step {exp.step} (spec from checkpoint metadata)")
    else:
        obs = {}
        if args.log_dir:
            obs = {"obs.enabled": True, "obs.sinks": ("jsonl",),
                   "obs.log_dir": args.log_dir, "obs.trace": args.trace,
                   # ~100 train rows whatever the budget (cap at the
                   # ObsSpec default cadence of 50)
                   "obs.log_every": max(1, min(50, args.steps // 100))}
        elif args.trace:
            ap.error("--trace needs --log-dir (traces land in "
                     "<log-dir>/trace)")
        guard = ({"guard.enabled": True, "guard.policy": args.guard}
                 if args.guard else {})
        spec = presets.get("quickstart").override(
            num_units=args.units or 128, total_steps=args.steps,
            eval_every=max(args.steps // 8, 1),
            srank_every=max(args.steps // 8, 1),
            **obs, **guard, **parse_overrides(args.override))
        exp = Experiment.from_spec(spec)

    res = exp.run(args.steps, progress=lambda s, r, m: print(
        f"step {s:6d}  eval return {r:9.1f}  "
        f"critic {m.get('critic_loss', 0):.3f}  aux {m.get('aux_loss', 0):.3f}"))
    print(f"\nparams={res.param_count:,}  max return={res.max_return:.1f}")
    print("effective-rank trace (srank of Q features):", res.sranks)
    if args.ckpt:
        exp.save(args.ckpt)
        print(f"checkpoint -> {args.ckpt}  (resume with --resume {args.ckpt})")
    if args.serve:
        serve_round_trip(exp)
    exp.close()
    if args.log_dir:
        print(f"telemetry -> {args.log_dir}/metrics.jsonl  "
              f"(summarize: python -m repro.obs.report {args.log_dir})")


if __name__ == "__main__":
    main()
