"""What every cell shares: the benchmark's files, the device, the weights,
the trace window, the per-layer readers and the result line.

Everything a cell is made of is data found by name:
``bench/configs/<config>.json`` (the full ``ExperimentSpec`` plus the
hyperparameters the reference needs), ``bench/traffic/<traffic>.json``
(its ``kind`` names ``bench/kinds/<kind>.py``), ``bench/limits/<workload>.json``
(the limits of the numbers that decide ``correct``) and
``bench/metrics/<metric>.py`` (one reader per per-layer metric).
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "bench_out"          # traces and scratch of a run (gitignored)


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, missing file)."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(workload: str) -> dict:
    """The workload entry with its config, traffic and limits loaded."""
    bm = benchmark()
    wl = {w["name"]: w for w in bm["workloads"]}.get(workload)
    if wl is None:
        raise BenchError(f"unknown workload {workload!r}")
    cfg_entry = {c["name"]: c for c in bm["configs"]}[wl["config"]]
    return {"workload": wl,
            "config": load_json(ROOT / cfg_entry["file"]),
            "traffic": load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
            "limits": load_json(BENCH / "limits" / f"{workload}.json"),
            "end_to_end": [m for m in bm["end_to_end"] if reports(m, workload)],
            "per_layer": [m for m in bm["per_layer"] if reports(m, workload)],
            "run_seconds": bm["run_seconds"]}


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (once per process)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py", f"bench_kind_{kind}")


def seed31(seed: int) -> int:
    """The run's seed folded into the non-negative int32 range the
    program's ``execution.seed`` and ``jax.random.key`` take."""
    return int(seed) % (2 ** 31 - 1)


# ------------------------------------------------------------------ device
def require_chips(n: int):
    """The devices of the cell; raises unless JAX sees ``n`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's default device is "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, RuntimeError):
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else 0}


class CompileCounter:
    """Counts XLA backend compiles (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.n = 0

        def listener(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


def enable_cache() -> None:
    """JAX's persistent compile cache at the checkout's fixed
    ``.jax_cache`` (or ``JAX_COMPILATION_CACHE_DIR``), for every program."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ----------------------------------------------------------------- weights
def make_params(template, init_alpha: float, key):
    """Seeded weights in the layout of ``template`` (a params shape tree):
    every ``w`` uniform in +-1/sqrt(fan_in), biases zero, ``log_alpha`` =
    log(init_alpha), target networks equal to their online networks."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    vals = []
    for i, (path, s) in enumerate(leaves):
        last = getattr(path[-1], "key", None)
        if last == "w":
            bound = 1.0 / math.sqrt(s.shape[0])
            vals.append(jax.random.uniform(jax.random.fold_in(key, i),
                                           s.shape, s.dtype, -bound, bound))
        elif last == "log_alpha":
            vals.append(jnp.full(s.shape, math.log(init_alpha), s.dtype))
        else:
            vals.append(jnp.zeros(s.shape, s.dtype))
    p = jax.tree_util.tree_unflatten(treedef, vals)
    p = dict(p, target_critics=p["critics"])
    if "ofenet" in p:
        p["ofenet"] = dict(p["ofenet"], target=p["ofenet"]["online"])
    return p


def params_template(cfg: dict):
    """Shapes of the program's params tree for a configuration file."""
    import jax
    from repro.rl.envs import make_env
    from repro.rl.experiment import ExperimentSpec
    from repro.rl.policy import algo_config
    from repro.rl.sac import sac_init
    spec = ExperimentSpec.from_dict(cfg["spec"])
    acfg = algo_config(spec, make_env(spec.env))
    return jax.eval_shape(lambda k: sac_init(k, acfg)["params"],
                          jax.random.key(0))


def weights_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed31(seed)), 0x5EED)


# ------------------------------------------------------------------- trace
class TraceWindow:
    """A ``jax.profiler`` trace of one stretch of the window; the stretch
    is marked by a host annotation the reduction reads its bounds from."""

    NAME = "bench.traced_window"

    def __init__(self, on: bool, workload: str):
        self.on = on
        self.dir = OUT / "trace" / workload
        self._ann = None

    def __enter__(self):
        if self.on:
            import shutil
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True, exist_ok=True)
            # the Python tracer's per-call events would slow the host side
            # (the load generator, the dispatch loop) several-fold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(self.NAME)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            self._ann.__exit__(*exc)
            jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        if not self.on:
            return None
        import trace_reduce
        red = trace_reduce.reduce_dir(self.dir, window=self.NAME)
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def read_per_layer(metrics: List[dict], ctx: dict) -> Dict[str, dict]:
    """Run each per-layer metric's reader; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in metrics:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ result
def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``correct`` over every number that has a limit, and the checks
    block: each number beside its limit (None: recorded, not compared)."""
    checks, ok = {}, True
    for k, v in numbers.items():
        lim = limits.get(k)
        checks[k] = {"value": v, "limit": lim}
        if lim is not None and not (math.isfinite(v) and v <= lim):
            ok = False
    missing = [k for k in limits if k not in numbers]
    for k in missing:             # a compared number that was not read fails
        checks[k] = {"value": None, "limit": limits[k]}
        ok = False
    return {"correct": ok, "checks": checks}


def emit(result: dict, checks: dict) -> None:
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "checks": checks}))
    sys.stdout.flush()


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def top(pairs: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]

