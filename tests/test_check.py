"""repro.check: per-rule fixture pairs, suppressions, baseline, self-scan."""
import json
import textwrap

import pytest

from repro.check import lint as lint_mod
from repro.check import report
from repro.check.dynamic import chunk_signatures
from repro.check.lint import lint_paths, lint_source
from repro.check.report import Finding


def lint(src, path="src/repro/rl/fixture.py", **kw):
    return lint_source(textwrap.dedent(src), path, **kw)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# --------------------------------------------------------------------- R001

BAD_R001 = """
    import time
    import jax

    @jax.jit
    def step(x):
        t = time.time()
        return x + t
"""

GOOD_R001 = """
    import time
    import jax

    @jax.jit
    def step(x, t):
        return x + t

    def driver(x):
        return step(x, time.time())   # host side: fine
"""


def test_r001_fires_on_clock_in_jit():
    fs = [f for f in lint(BAD_R001) if f.rule == "R001"]
    assert len(fs) == 1 and "time.time" in fs[0].message
    assert fs[0].line == 7


def test_r001_clean_on_host_side_clock():
    assert not [f for f in lint(GOOD_R001) if f.rule == "R001"]


def test_r001_reaches_through_helper_and_partial():
    # impurity in a helper that a scanned body calls, traced via
    # functools.partial(jax.jit, ...) style indirection
    src = """
        import jax
        import numpy as np

        def noise():
            return np.random.rand()

        def body(c, x):
            return c + noise(), x

        def run(xs):
            return jax.lax.scan(body, 0.0, xs)
    """
    fs = [f for f in lint(src) if f.rule == "R001"]
    assert len(fs) == 1 and "np.random.rand" in fs[0].message


def test_r001_resolves_import_aliases():
    src = """
        import jax
        from numpy import random as nprand

        @jax.jit
        def step(x):
            return x + nprand.normal()
    """
    assert rules_of(lint(src)) == ["R001"]


# --------------------------------------------------------------------- R002

BAD_R002 = """
    import jax

    def init(key):
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))
        return a + b
"""

GOOD_R002 = """
    import jax

    def init(key):
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (3,))
        b = jax.random.uniform(k2, (3,))
        return a + b
"""


def test_r002_fires_on_key_reuse():
    fs = [f for f in lint(BAD_R002) if f.rule == "R002"]
    assert len(fs) == 1 and "'key'" in fs[0].message


def test_r002_clean_after_split():
    assert not [f for f in lint(GOOD_R002) if f.rule == "R002"]


def test_r002_fold_in_rebind_is_clean():
    src = """
        import jax

        def roll(key, step):
            key = jax.random.fold_in(key, step)
            return jax.random.normal(key, ())
    """
    assert not [f for f in lint(src) if f.rule == "R002"]


def test_r002_exclusive_branches_are_not_reuse():
    # the replay _sample_raw shape: one consumption per if/else arm
    src = """
        import jax

        def sample(cfg, key, n):
            if cfg.uniform:
                return jax.random.randint(key, (n,), 0, 10)
            return jax.random.uniform(key, (n,))
    """
    assert not [f for f in lint(src) if f.rule == "R002"]


def test_r002_reuse_after_both_branches_fires():
    src = """
        import jax

        def sample(flag, key, n):
            if flag:
                a = jax.random.normal(key, (n,))
            else:
                a = jax.random.uniform(key, (n,))
            return a + jax.random.normal(key, (n,))
    """
    assert len([f for f in lint(src) if f.rule == "R002"]) == 1


def test_r002_loop_reuse_without_rebind_fires():
    src = """
        import jax

        def rollout(key, n):
            outs = []
            for i in range(n):
                outs.append(jax.random.normal(key, ()))
            return outs
    """
    assert [f for f in lint(src) if f.rule == "R002"]


# --------------------------------------------------------------------- R003

BAD_R003 = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        y = jnp.sum(x)
        if y > 0:
            return x
        return -x
"""

GOOD_R003 = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        y = jnp.sum(x)
        return jnp.where(y > 0, x, -x)
"""


def test_r003_fires_on_tracer_branch():
    fs = [f for f in lint(BAD_R003) if f.rule == "R003"]
    assert len(fs) == 1 and "if" in fs[0].message


def test_r003_clean_on_where():
    assert not [f for f in lint(GOOD_R003) if f.rule == "R003"]


def test_r003_static_config_params_are_clean():
    # the kernels idiom: python-level flags select code paths at trace time
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def attend(q, causal=True, backend="xla"):
            s = jnp.dot(q, q.T)
            if causal:
                s = jnp.tril(s)
            if backend == "xla":
                return s
            return s * 2
    """
    assert not [f for f in lint(src) if f.rule == "R003"]


def test_r003_shape_and_dtype_branches_are_clean():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def norm(x):
            if x.ndim == 2 and jnp.issubdtype(x.dtype, jnp.floating):
                return x / x.shape[0]
            return x
    """
    assert not [f for f in lint(src) if f.rule == "R003"]


def test_r003_array_param_branch_fires():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def clip(x):
            assert x > 0
            return jnp.log(x)
    """
    assert [f for f in lint(src) if f.rule == "R003"]


# --------------------------------------------------------------------- R004

BAD_R004 = """
    import jax.numpy as jnp

    def drive(state):
        loss = jnp.mean(state)
        if float(loss) > 1e3:
            raise RuntimeError("diverged")
        return state
"""

GOOD_R004 = """
    import jax

    def drive(state):
        loss = jax.device_get(state)   # explicit epilogue barrier
        return float(loss)
"""


def test_r004_fires_in_loop_module():
    fs = [f for f in lint(BAD_R004, path="src/repro/rl/runner.py")
          if f.rule == "R004"]
    assert len(fs) == 1 and "float" in fs[0].message


def test_r004_device_get_is_sanctioned():
    assert not [f for f in lint(GOOD_R004, path="src/repro/rl/runner.py")
                if f.rule == "R004"]


def test_r004_item_fires():
    src = """
        def drive(out):
            return out["srank"].item()
    """
    assert [f for f in lint(src, path="src/repro/replay/device.py")
            if f.rule == "R004"]


def test_r004_silent_outside_loop_modules_and_traces():
    assert not [f for f in lint(BAD_R004, path="src/repro/obs/report.py")
                if f.rule == "R004"]


# --------------------------------------------------------------------- R005

def test_r005_flags_unreachable_module(tmp_path):
    (tmp_path / ".git").mkdir()
    src = tmp_path / "src" / "pkg"
    src.mkdir(parents=True)
    (src / "used.py").write_text("VALUE = 1\n")
    (src / "orphan.py").write_text("import math\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_used.py").write_text("from pkg.used import VALUE\n")
    fs = lint_paths([str(tmp_path / "src")], root=str(tmp_path))
    dead = [f for f in fs if f.rule == "R005"]
    assert [f.file for f in dead] == ["src/pkg/orphan.py"]


def test_r005_main_block_is_an_entrypoint(tmp_path):
    (tmp_path / ".git").mkdir()
    src = tmp_path / "src" / "pkg"
    src.mkdir(parents=True)
    (src / "cli.py").write_text(
        "def main():\n    pass\n\n"
        "if __name__ == \"__main__\":\n    main()\n")
    fs = lint_paths([str(tmp_path / "src")], root=str(tmp_path))
    assert not [f for f in fs if f.rule == "R005"]


# --------------------------------------------------------------------- R006

BAD_R006 = """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class TrainSpec:
        lr: float = 1e-3
        batch: int = 32

        def __post_init__(self):
            if self.lr <= 0:
                raise ValueError("lr must be positive")
"""

GOOD_R006 = """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class TrainSpec:
        lr: float = 1e-3
        batch: int = 32

        def __post_init__(self):
            if self.lr <= 0:
                raise ValueError("lr must be positive")
            if self.batch <= 0:
                raise ValueError("batch must be positive")
"""


def test_r006_fires_on_uncovered_field():
    fs = [f for f in lint(BAD_R006) if f.rule == "R006"]
    assert len(fs) == 1 and "TrainSpec.batch" in fs[0].message


def test_r006_clean_when_all_fields_checked():
    assert not [f for f in lint(GOOD_R006) if f.rule == "R006"]


def test_r006_fires_on_missing_validator():
    src = """
        import dataclasses

        @dataclasses.dataclass
        class RunSpec:
            steps: int = 10
    """
    fs = [f for f in lint(src) if f.rule == "R006"]
    assert len(fs) == 1 and "no __post_init__/validate" in fs[0].message


def test_r006_table_driven_validator_covers(tmp_path):
    # the ExperimentSpec shape: sections checked via a module-level table
    src = """
        import dataclasses

        _SECTIONS = (("alpha", int), ("beta", float))

        @dataclasses.dataclass
        class TableSpec:
            alpha: int = 1
            beta: float = 2.0

            def __post_init__(self):
                for name, cls in _SECTIONS:
                    if not isinstance(getattr(self, name), cls):
                        raise ValueError(name)
    """
    assert not [f for f in lint(src) if f.rule == "R006"]


def test_r006_ignores_non_spec_dataclasses():
    src = """
        import dataclasses

        @dataclasses.dataclass
        class Record:
            value: int = 0
    """
    assert not [f for f in lint(src) if f.rule == "R006"]


# ------------------------------------------------------------- suppressions

def test_suppression_with_reason_silences():
    src = BAD_R001.replace(
        "t = time.time()",
        "t = time.time()  # check: disable=R001 -- trace-time stamp is "
        "intentional here")
    assert not lint(src)


def test_suppression_comment_above_silences():
    src = BAD_R001.replace(
        "t = time.time()",
        "# check: disable=R001 -- trace-time stamp is intentional\n"
        "        t = time.time()")
    assert not lint(src)


def test_suppression_without_reason_is_r000():
    src = BAD_R001.replace("t = time.time()",
                           "t = time.time()  # check: disable=R001")
    assert rules_of(lint(src)) == ["R000", "R001"]


def test_suppression_only_silences_named_rule():
    src = BAD_R003.replace(
        "if y > 0:",
        "if y > 0:  # check: disable=R001 -- wrong rule id")
    assert rules_of(lint(src)) == ["R003"]


# ----------------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    f = Finding(rule="R001", file="src/x.py", line=3, message="m",
                hint="h", snippet="t = time.time()")
    path = tmp_path / "b.json"
    report.write_baseline([f], path, reason="legacy")
    loaded = report.load_baseline(path)
    assert loaded == {("src/x.py", "R001", "t = time.time()"): "legacy"}
    new, old = report.split_new([f], loaded)
    assert not new and old == [f]
    # drifted line number, same snippet -> still grandfathered
    moved = Finding(rule="R001", file="src/x.py", line=99, message="m",
                    hint="h", snippet="t = time.time()")
    new, old = report.split_new([moved], loaded)
    assert not new and old == [moved]
    # edited snippet -> resurfaces
    edited = Finding(rule="R001", file="src/x.py", line=3, message="m",
                     hint="h", snippet="t2 = time.time()")
    new, _ = report.split_new([edited], loaded)
    assert new == [edited]


def test_baseline_requires_reason(tmp_path):
    path = tmp_path / "b.json"
    path.write_text(json.dumps({"version": 1, "findings": [
        {"file": "a.py", "rule": "R001", "snippet": "x", "line": 1}]}))
    with pytest.raises(report.BaselineError):
        report.load_baseline(path)


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(BAD_R001))
    (tmp_path / ".git").mkdir()
    assert lint_mod.main([str(bad), "--no-dead"]) == 1
    base = tmp_path / "check_baseline.json"
    assert lint_mod.main([str(bad), "--no-dead", "--write-baseline",
                          "--baseline", str(base)]) == 0
    assert lint_mod.main([str(bad), "--no-dead",
                          "--baseline", str(base)]) == 0
    capsys.readouterr()


# ------------------------------------------------------ repo-level contract

def test_self_scan_repo_is_clean():
    """src/ is clean modulo check_baseline.json — the acceptance gate."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = lint_paths([os.path.join(root, "src")], root=root)
    base_path = os.path.join(root, "check_baseline.json")
    baseline = report.load_baseline(base_path) \
        if os.path.exists(base_path) else None
    new, _ = report.split_new(findings, baseline)
    assert not new, report.render(new)


def test_live_rl_guard_replay_obs_have_zero_finding_baseline():
    """The live subsystems start at zero findings — even grandfathered."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_path = os.path.join(root, "check_baseline.json")
    if not os.path.exists(base_path):
        return
    for (file, rule, _snip), _reason in report.load_baseline(
            base_path).items():
        assert not file.startswith(("src/repro/rl/", "src/repro/replay/",
                                    "src/repro/guard/", "src/repro/obs/")), \
            f"{rule} grandfathered in live module {file}"


def test_chunk_signature_prediction_matches_trainer_cache():
    """The D002 sentinel's scheduler replica agrees with the live driver."""
    jax = pytest.importorskip("jax")
    del jax
    from repro.rl import presets
    from repro.rl.experiment import Experiment

    spec = presets.get("smoke").override(total_steps=10, eval_every=4,
                                         srank_every=5)
    exp = Experiment.from_spec(spec)
    exp.run()
    predicted = set(chunk_signatures(0, 10, 4, 5))
    assert set(exp.trainer._chunks) == predicted


def test_chunk_signatures_schedule():
    # eval every 4, srank every 5, 10 steps: stops at 4, 5, 8, 10
    assert chunk_signatures(0, 10, 4, 5) == [
        (4, True, False), (1, False, True), (3, True, False),
        (2, False, True)]
    # resume mid-schedule: absolute multiples, not relative
    assert chunk_signatures(6, 10, 4, 0) == [(2, True, False),
                                             (2, False, False)]
