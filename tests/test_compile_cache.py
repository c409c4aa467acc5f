"""The persistent compile cache: placed from outside, or at a fixed path.

Each case runs in a fresh interpreter, so turning the cache on never leaks
into the test process (tests never run with it on).
"""
import ast
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_PROBE = r"""
import jax
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(cache_env):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    chosen, configured = _probe(str(tmp_path / "cc"))
    assert chosen == configured == str(tmp_path / "cc")


def test_cache_dir_defaults_to_fixed_checkout_path():
    chosen, configured = _probe(None)
    assert chosen == configured == str(ROOT / ".jax_cache")


ENTRY_POINTS = ["chip_smoke.py", "benchmarks/run.py",
                "benchmarks/loop_fusion.py", "examples/quickstart.py",
                "examples/rl_distributed.py", "examples/width_study.py",
                "src/repro/launch/serve_policy.py",
                "src/repro/guard/supervise.py", "src/repro/check/dynamic.py"]


@pytest.mark.parametrize("path", ENTRY_POINTS)
def test_entry_point_enables_compile_cache(path):
    tree = ast.parse((ROOT / path).read_text())
    called = {n.func.id for n in ast.walk(tree) if isinstance(n, ast.Call)
              and isinstance(n.func, ast.Name)}
    assert "enable_compile_cache" in called, path
