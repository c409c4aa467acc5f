"""In-loop health guards: detect divergence, decide halt / skip / rollback.

``GuardSpec`` is the ``guard`` section of ``ExperimentSpec``. When enabled,
the loop drivers force the per-step scalar stream on (the same stacked scan
outputs the obs subsystem consumes — emitting them is bitwise-invisible to
training, the PR-6 contract) and hand each chunk's stream plus the live
state to a ``Monitor``:

* **non-finite stream**  — any watched scalar (losses, grad norms, alpha,
  ...) going NaN/inf; caught at the exact offending step from the stacked
  stream, one step after a NaN first enters params/grads (the update that
  poisons the params still computes finite losses from the pre-update
  values).
* **non-finite params** — an all-``isfinite`` reduction over the agent
  params (one tiny jitted program per chunk; per-member under vmap for
  fleets).
* **loss spikes**       — ``spike_key`` exceeding ``spike_factor`` x the
  rolling-window median (host-side, absolute values).
* **srank collapse**    — latest effective rank below ``srank_collapse`` x
  the run's peak (needs ``eval.srank_every`` > 0).

Detection is pure observation: a guarded run with no violations is
bitwise-identical to an unguarded one. On violation the driver applies
``GuardSpec.policy``:

* ``halt``     — raise ``GuardViolation`` (the supervisor turns this into
  an incident report).
* ``skip``     — discard the offending segment (restore the pre-segment
  in-memory snapshot), perturb the PRNG key with
  ``fold_in(key, recovery_ordinal)`` and re-run the segment. Solo only.
* ``rollback`` — restore the last GOOD durable checkpoint from the
  attached ``repro.guard.store.DurableStore``, perturb the key the same
  way, and continue. In a ``Fleet`` the rollback is PER MEMBER through the
  segment-end ``_tree_where`` select, so healthy neighbors stay bitwise
  untouched.

Recovery is deterministic: the post-recovery trajectory is a pure function
of (restored state, recovery ordinal) — ``fold_in(key, n)`` for the n-th
recovery — so tests can reconstruct it exactly (tests/test_guard.py pins
the solo case leaf-for-leaf). ``max_recoveries`` bounds the budget; once
spent, the next violation raises regardless of policy.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

POLICIES = ("halt", "skip", "rollback")

_MIN_SPIKE_HISTORY = 8           # median needs some history before judging


@dataclasses.dataclass(frozen=True)
class GuardSpec:
    """The ``guard`` section of ``ExperimentSpec`` (validated standalone so
    ``repro.guard`` never imports ``repro.rl`` — no import cycle)."""
    enabled: bool = False
    policy: str = "halt"           # halt | skip | rollback
    check_params: bool = True      # all-finite reduction on agent params
    spike_factor: float = 0.0      # >0: flag spike_key > factor x median
    spike_key: str = "critic_loss"
    spike_window: int = 64         # rolling median window (host-side)
    srank_collapse: float = 0.0    # >0: flag srank < frac x run peak
    max_recoveries: int = 3        # skip/rollback budget per run

    def __post_init__(self):
        if not isinstance(self.enabled, (bool, np.bool_)):
            raise ValueError(f"guard.enabled={self.enabled!r} must be a "
                             f"bool")
        if self.policy not in POLICIES:
            raise ValueError(f"guard.policy={self.policy!r} is not one of "
                             f"{POLICIES}")
        if not isinstance(self.check_params, (bool, np.bool_)):
            raise ValueError(f"guard.check_params={self.check_params!r} "
                             f"must be a bool")
        if not self.spike_key or not isinstance(self.spike_key, str):
            raise ValueError(f"guard.spike_key={self.spike_key!r} must be "
                             f"a non-empty metric-stream key")
        for f in ("spike_factor", "srank_collapse"):
            v = getattr(self, f)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                raise ValueError(f"guard.{f}={v!r} must be a number >= 0")
        if self.srank_collapse >= 1.0:
            raise ValueError(f"guard.srank_collapse={self.srank_collapse!r} "
                             f"must be < 1 (a fraction of the peak)")
        for f, lo in (("spike_window", 2), ("max_recoveries", 0)):
            v = getattr(self, f)
            if not isinstance(v, (int, np.integer)) or isinstance(v, bool) \
                    or v < lo:
                raise ValueError(f"guard.{f}={v!r} must be an int >= {lo}")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One detected health violation (a member of ``GuardViolation`` and of
    the supervisor's incident report)."""
    step: int                      # absolute learner step of detection
    reason: str                    # nonfinite_stream|nonfinite_params|
                                   # spike|srank_collapse
    detail: str = ""
    member: Optional[int] = None   # fleet member index (None: solo)
    value: Optional[float] = None

    def as_dict(self) -> Dict[str, Any]:
        d = {"step": self.step, "reason": self.reason, "detail": self.detail}
        if self.member is not None:
            d["member"] = self.member
        if self.value is not None and np.isfinite(self.value):
            d["value"] = float(self.value)
        return d


class GuardViolation(RuntimeError):
    """Raised when policy is ``halt``, when the recovery budget is spent,
    or when skip/rollback cannot proceed (no snapshot / no good
    checkpoint). Carries the violations for the incident report."""

    def __init__(self, message: str, violations: List[Violation],
                 recoveries: int = 0):
        super().__init__(message)
        self.violations = list(violations)
        self.recoveries = recoveries

    @property
    def step(self) -> Optional[int]:
        return self.violations[0].step if self.violations else None


# ------------------------------------------------------------ health fns

def _float_leaves(tree) -> List[jax.Array]:
    return [x for x in jax.tree_util.tree_leaves(tree)
            if hasattr(x, "dtype")
            and jnp.issubdtype(x.dtype, jnp.floating)]


@jax.jit
def _finite_all(leaves):
    ok = jnp.bool_(True)
    for x in leaves:
        ok &= jnp.isfinite(x).all()
    return ok


@jax.jit
def _finite_per_member(leaves):
    m = leaves[0].shape[0]
    ok = jnp.ones((m,), bool)
    for x in leaves:
        ok &= jnp.isfinite(x.reshape(x.shape[0], -1)).all(axis=1)
    return ok


def all_finite(tree) -> bool:
    """True when every floating leaf of ``tree`` is finite everywhere (one
    jitted reduction; non-float leaves — ints, PRNG keys — are skipped)."""
    leaves = _float_leaves(tree)
    return bool(_finite_all(leaves)) if leaves else True


def member_finite(tree) -> np.ndarray:
    """Per-member all-finite over a member-stacked tree: ``(M,)`` bool,
    reducing every axis of each floating leaf except the leading member
    axis."""
    leaves = _float_leaves(tree)
    if not leaves:
        raise ValueError("member_finite: tree has no floating leaves")
    return np.asarray(_finite_per_member(leaves))


# --------------------------------------------------------------- monitor

class Monitor:
    """Host-side detection state for one run: the rolling spike window, the
    srank peak, and the recovery budget. Drivers call the ``check_*``
    methods after each segment and route any returned violations through
    their policy handler."""

    def __init__(self, spec: GuardSpec):
        self.spec = spec
        self.recoveries = 0
        self._spike_hist: deque = deque(maxlen=spec.spike_window)

    # ------------------------------------------------------------ checks
    def check_stream(self, start_step: int,
                     stream: Mapping[str, np.ndarray],
                     member: Optional[int] = None) -> List[Violation]:
        """Scan one segment's stacked scalar stream (host arrays covering
        absolute steps ``start_step+1 .. start_step+n``) for non-finite
        values and spikes."""
        out: List[Violation] = []
        for key in sorted(stream):
            v = np.asarray(stream[key], np.float64)
            bad = ~np.isfinite(v)
            if bad.any():
                i = int(np.argmax(bad))
                out.append(Violation(
                    step=start_step + i + 1, reason="nonfinite_stream",
                    detail=f"{key} is {v[i]!r}", member=member,
                    value=float(v[i])))
        spec = self.spec
        if spec.spike_factor and spec.spike_key in stream:
            vals = np.abs(np.asarray(stream[spec.spike_key], np.float64))
            for i, v in enumerate(vals):
                if not np.isfinite(v):
                    continue       # already reported above
                if len(self._spike_hist) >= _MIN_SPIKE_HISTORY:
                    med = float(np.median(self._spike_hist))
                    if med > 0 and v > spec.spike_factor * med:
                        out.append(Violation(
                            step=start_step + i + 1, reason="spike",
                            detail=f"{spec.spike_key}={v:.4g} > "
                                   f"{spec.spike_factor:g} x median "
                                   f"{med:.4g}", member=member,
                            value=float(v)))
                        continue   # a spike does not poison the window
                self._spike_hist.append(v)
        return out

    def check_params(self, step: int, params,
                     member: Optional[int] = None) -> List[Violation]:
        if not self.spec.check_params:
            return []
        if not all_finite(params):
            return [Violation(step=step, reason="nonfinite_params",
                              detail="non-finite value in agent params",
                              member=member)]
        return []

    def check_member_params(self, step: int, params) -> List[Violation]:
        """Fleet variant: one violation per member with non-finite params
        (params stacked on a leading member axis)."""
        if not self.spec.check_params:
            return []
        ok = member_finite(params)
        return [Violation(step=step, reason="nonfinite_params",
                          detail="non-finite value in agent params",
                          member=int(m))
                for m in np.nonzero(~ok)[0]]

    def check_srank(self, step: int, sranks,
                    member: Optional[int] = None) -> List[Violation]:
        frac = self.spec.srank_collapse
        if not frac or len(sranks) < 2:
            return []
        peak, last = max(sranks), sranks[-1]
        if peak > 0 and last < frac * peak:
            return [Violation(step=step, reason="srank_collapse",
                              detail=f"srank {last} < {frac:g} x peak "
                                     f"{peak}", member=member,
                              value=float(last))]
        return []

    # ---------------------------------------------------------- recovery
    def spend_recovery(self, violations: List[Violation]) -> int:
        """Consume one unit of the recovery budget; returns the recovery
        ORDINAL (1-based — the ``fold_in`` perturbation value). Raises
        ``GuardViolation`` when the budget is already spent."""
        if self.recoveries >= self.spec.max_recoveries:
            raise GuardViolation(
                f"guard: recovery budget spent "
                f"({self.spec.max_recoveries} {self.spec.policy}(s)); "
                f"latest: {[v.as_dict() for v in violations]}",
                violations, self.recoveries)
        self.recoveries += 1
        return self.recoveries
