"""Pure-JAX continuous-control environments (MuJoCo stand-ins; DESIGN.md §7).

The container cannot run MuJoCo, so the paper's locomotion suite is replaced
with analytic rigid-body tasks implemented directly in JAX. They are fully
vmappable/jittable — on TPU this makes the *simulator itself* a device
program, which is the TPU-native analogue of the paper's CPU actor processes.

Env API (functional):
    env.reset(key)                 -> EnvState
    env.step(state, action)        -> (EnvState, obs, reward, done)
    env.obs(state)                 -> observation
    env.obs_dim / act_dim / max_episode_steps

All dynamics use semi-implicit Euler integration.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class EnvState(NamedTuple):
    q: jax.Array            # generalized positions
    qd: jax.Array           # generalized velocities
    t: jax.Array            # step counter (int32)
    key: jax.Array


@dataclasses.dataclass(frozen=True)
class EnvSpec:
    name: str
    obs_dim: int
    act_dim: int
    max_episode_steps: int
    reset: Callable
    step: Callable
    obs: Callable

    def __post_init__(self):
        if not self.name or not isinstance(self.name, str):
            raise ValueError(f"env name={self.name!r} must be a non-empty "
                             f"string")
        for field in ("obs_dim", "act_dim", "max_episode_steps"):
            v = getattr(self, field)
            if not isinstance(v, int) or v <= 0:
                raise ValueError(f"env {self.name}: {field}={v!r} must be "
                                 f"a positive int")
        for field in ("reset", "step", "obs"):
            if not callable(getattr(self, field)):
                raise ValueError(f"env {self.name}: {field} must be "
                                 f"callable")


def _mk_state(key, q, qd):
    return EnvState(q=q, qd=qd, t=jnp.int32(0), key=key)


# ---------------------------------------------------------------------------
# Pendulum swing-up (obs: cos, sin, thdot)
# ---------------------------------------------------------------------------

def make_pendulum() -> EnvSpec:
    g, m, l, dt = 10.0, 1.0, 1.0, 0.05
    max_speed, max_torque = 8.0, 2.0

    def obs(s: EnvState):
        th = s.q[0]
        return jnp.stack([jnp.cos(th), jnp.sin(th), s.qd[0] / max_speed])

    def reset(key):
        k1, k2, k3 = jax.random.split(key, 3)
        th = jax.random.uniform(k1, (), minval=-jnp.pi, maxval=jnp.pi)
        thd = jax.random.uniform(k2, (), minval=-1.0, maxval=1.0)
        return _mk_state(k3, jnp.array([th]), jnp.array([thd]))

    def step(s: EnvState, a: jax.Array):
        u = jnp.clip(a[0], -1, 1) * max_torque
        th, thd = s.q[0], s.qd[0]
        norm_th = jnp.mod(th + jnp.pi, 2 * jnp.pi) - jnp.pi
        cost = norm_th ** 2 + 0.1 * thd ** 2 + 0.001 * u ** 2
        thd = jnp.clip(thd + (3 * g / (2 * l) * jnp.sin(th)
                              + 3.0 / (m * l ** 2) * u) * dt,
                       -max_speed, max_speed)
        th = th + thd * dt
        ns = EnvState(q=jnp.array([th]), qd=jnp.array([thd]),
                      t=s.t + 1, key=s.key)
        return ns, obs(ns), -cost, jnp.bool_(False)

    return EnvSpec("pendulum", 3, 1, 200, reset, step, obs)


# ---------------------------------------------------------------------------
# Cartpole swing-up (obs: x, xd, cos, sin, thd)
# ---------------------------------------------------------------------------

def make_cartpole_swingup() -> EnvSpec:
    mc, mp, l, g, dt = 1.0, 0.1, 0.5, 9.8, 0.02
    force_mag = 10.0

    def obs(s: EnvState):
        x, th = s.q
        xd, thd = s.qd
        return jnp.stack([x / 2.4, xd, jnp.cos(th), jnp.sin(th), thd])

    def reset(key):
        k1, k2 = jax.random.split(key)
        q0 = jnp.array([0.0, jnp.pi]) + 0.05 * jax.random.normal(k1, (2,))
        return _mk_state(k2, q0, jnp.zeros(2))

    def step(s: EnvState, a: jax.Array):
        f = jnp.clip(a[0], -1, 1) * force_mag
        x, th = s.q
        xd, thd = s.qd
        sin, cos = jnp.sin(th), jnp.cos(th)
        tmp = (f + mp * l * thd ** 2 * sin) / (mc + mp)
        thacc = (g * sin - cos * tmp) / (l * (4.0 / 3 - mp * cos ** 2 / (mc + mp)))
        xacc = tmp - mp * l * thacc * cos / (mc + mp)
        xd = xd + xacc * dt
        x = jnp.clip(x + xd * dt, -2.4, 2.4)
        thd = thd + thacc * dt
        th = th + thd * dt
        ns = EnvState(q=jnp.array([x, th]), qd=jnp.array([xd, thd]),
                      t=s.t + 1, key=s.key)
        upright = jnp.cos(th)
        reward = upright - 0.01 * xd ** 2 - 0.001 * f ** 2 - 0.1 * jnp.abs(x)
        return ns, obs(ns), reward, jnp.bool_(False)

    return EnvSpec("cartpole_swingup", 5, 1, 250, reset, step, obs)


# ---------------------------------------------------------------------------
# Reacher-2: 2-link arm reaching a random target
# obs: cos/sin of 2 joints, 2 joint vels, target xy, fingertip-target delta
# ---------------------------------------------------------------------------

def make_reacher2() -> EnvSpec:
    l1, l2, dt = 0.1, 0.11, 0.02

    def fingertip(q):
        x = l1 * jnp.cos(q[0]) + l2 * jnp.cos(q[0] + q[1])
        y = l1 * jnp.sin(q[0]) + l2 * jnp.sin(q[0] + q[1])
        return jnp.array([x, y])

    def obs(s: EnvState):
        tgt = s.q[2:4]
        ft = fingertip(s.q[:2])
        return jnp.concatenate([jnp.cos(s.q[:2]), jnp.sin(s.q[:2]),
                                s.qd[:2], tgt, ft - tgt])

    def reset(key):
        k1, k2, k3 = jax.random.split(key, 3)
        joints = jax.random.uniform(k1, (2,), minval=-jnp.pi, maxval=jnp.pi)
        r = jax.random.uniform(k2, (), minval=0.05, maxval=0.2)
        ang = jax.random.uniform(k3, (), minval=-jnp.pi, maxval=jnp.pi)
        tgt = jnp.array([r * jnp.cos(ang), r * jnp.sin(ang)])
        return _mk_state(k3, jnp.concatenate([joints, tgt]),
                         jnp.zeros(4))

    def step(s: EnvState, a: jax.Array):
        u = jnp.clip(a, -1, 1) * 0.5
        qd = s.qd[:2] * 0.95 + u * dt * 40.0
        q = s.q[:2] + qd * dt
        ns = EnvState(q=jnp.concatenate([q, s.q[2:4]]),
                      qd=jnp.concatenate([qd, jnp.zeros(2)]),
                      t=s.t + 1, key=s.key)
        dist = jnp.linalg.norm(fingertip(q) - s.q[2:4])
        reward = -dist - 0.01 * jnp.sum(jnp.square(u))
        return ns, obs(ns), reward, jnp.bool_(False)

    return EnvSpec("reacher2", 10, 2, 100, reset, step, obs)


# ---------------------------------------------------------------------------
# PointMass-2D with drag: reach the origin from random start
# ---------------------------------------------------------------------------

def make_pointmass() -> EnvSpec:
    dt = 0.05

    def obs(s: EnvState):
        return jnp.concatenate([s.q, s.qd])

    def reset(key):
        k1, k2 = jax.random.split(key)
        q = jax.random.uniform(k1, (2,), minval=-1.0, maxval=1.0)
        return _mk_state(k2, q, jnp.zeros(2))

    def step(s: EnvState, a: jax.Array):
        u = jnp.clip(a, -1, 1)
        qd = s.qd * 0.9 + u * dt * 4.0
        q = s.q + qd * dt
        ns = EnvState(q=q, qd=qd, t=s.t + 1, key=s.key)
        reward = -jnp.linalg.norm(q) - 0.05 * jnp.sum(jnp.square(u))
        return ns, obs(ns), reward, jnp.bool_(False)

    return EnvSpec("pointmass", 4, 2, 100, reset, step, obs)


# ---------------------------------------------------------------------------
# Acrobot (continuous torque on second joint), swing-up reward
# ---------------------------------------------------------------------------

def make_acrobot() -> EnvSpec:
    m1 = m2 = 1.0
    l1 = 1.0
    lc1 = lc2 = 0.5
    i1 = i2 = 1.0
    g, dt = 9.8, 0.05

    def obs(s: EnvState):
        return jnp.stack([jnp.cos(s.q[0]), jnp.sin(s.q[0]),
                          jnp.cos(s.q[1]), jnp.sin(s.q[1]),
                          s.qd[0] / 5.0, s.qd[1] / 10.0])

    def reset(key):
        k1, k2 = jax.random.split(key)
        q = 0.1 * jax.random.normal(k1, (2,))
        return _mk_state(k2, q, jnp.zeros(2))

    def step(s: EnvState, a: jax.Array):
        tau = jnp.clip(a[0], -1, 1) * 2.0
        th1, th2 = s.q
        d1, d2 = s.qd
        d2_ = m2 * (lc2 ** 2 + l1 * lc2 * jnp.cos(th2)) + i2
        dmat = m1 * lc1 ** 2 + m2 * (l1 ** 2 + lc2 ** 2
                                     + 2 * l1 * lc2 * jnp.cos(th2)) + i1 + i2
        phi2 = m2 * lc2 * g * jnp.cos(th1 + th2 - jnp.pi / 2)
        phi1 = (-m2 * l1 * lc2 * d2 ** 2 * jnp.sin(th2)
                - 2 * m2 * l1 * lc2 * d2 * d1 * jnp.sin(th2)
                + (m1 * lc1 + m2 * l1) * g * jnp.cos(th1 - jnp.pi / 2) + phi2)
        dd2 = (tau + d2_ / dmat * phi1 - m2 * l1 * lc2 * d1 ** 2
               * jnp.sin(th2) - phi2) / (m2 * lc2 ** 2 + i2 - d2_ ** 2 / dmat)
        dd1 = -(d2_ * dd2 + phi1) / dmat
        d1 = jnp.clip(d1 + dd1 * dt, -5, 5)
        d2 = jnp.clip(d2 + dd2 * dt, -10, 10)
        th1 = th1 + d1 * dt
        th2 = th2 + d2 * dt
        ns = EnvState(q=jnp.array([th1, th2]), qd=jnp.array([d1, d2]),
                      t=s.t + 1, key=s.key)
        height = -jnp.cos(th1) - jnp.cos(th1 + th2)
        return ns, obs(ns), height - 0.01 * tau ** 2, jnp.bool_(False)

    return EnvSpec("acrobot", 6, 1, 200, reset, step, obs)


# ---------------------------------------------------------------------------
# Quadruped: a torso on four two-joint legs, at Ant-v2's observation and
# action widths (111 / 8) and reward terms
# ---------------------------------------------------------------------------

QUADRUPED = dict(
    substeps=5, dt=0.01,                 # Ant-v2: frame_skip 5 x 0.01 s
    gear=150.0, inertia=20.0, damping=10.0,   # joint: tau = gear * a
    hip_range=0.52, ankle_range=(0.52, 1.22),  # rad, Ant-v2's 30 and 70 deg
    leg_angles=(0.25, 0.75, 1.25, 1.75),      # x pi: the legs' directions
    upper=0.28, lower=0.57,              # horizontal reach, shin length (m)
    mass=1.0, gravity=9.81, tilt_inertia=0.5, tilt_damping=2.0,
    contact_k=400.0, contact_d=10.0, friction=1.0, yaw_gain=0.5,
    z0=0.75, ankle0=1.0, healthy_z=(0.2, 1.0), episode=1000)


def make_quadruped() -> EnvSpec:
    """A torso on four legs (hip in the horizontal plane, ankle lifting the
    shin), at Ant-v2's widths: observation 13 positions (torso height,
    orientation quaternion, 8 joint angles; no x/y), 14 velocities (torso
    linear and angular, 8 joints) and 84 external contact-force entries
    (14 bodies x [torque, force], Ant-v2's ``cfrc_ext`` layout, only the
    four feet non-zero) clipped to [-1, 1]; action 8 joint torques in
    [-1, 1]; dt 0.05 s as 5 steps of 0.01 s.

    Dynamics, semi-implicit Euler per step, all small-angle:

    * joints: ``qdd = (gear a - damping qd - contact load) / inertia``,
      clipped to their ranges (velocity zeroed at a stop);
    * a foot sits ``lower sin(ankle)`` below the torso, tilted by roll and
      pitch; below the ground it pushes with ``k pen - d vz`` (no pull);
    * the torso rises with the feet's normal forces, tilts with their
      moments (damped), and slides toward the velocity the grounded feet's
      hip swing drives it to, each foot weighted by its normal force
      (friction); hip swing on the ground also turns it (yaw).

    Reward (Ant-v2): forward velocity - 0.5 |a|^2 - 5e-4 |clip(cfrc)|^2 +
    1 while the torso height is in [0.2, 1]. Episodes end at 1 000 steps
    only (``done`` is never set)."""
    c = QUADRUPED
    dt = c["dt"]
    phi = jnp.pi * jnp.asarray(c["leg_angles"], jnp.float32)
    lo = jnp.asarray([-c["hip_range"]] * 4 + [c["ankle_range"][0]] * 4)
    hi = jnp.asarray([c["hip_range"]] * 4 + [c["ankle_range"][1]] * 4)

    def feet(q):
        """Feet offsets from the torso (x, y, z; world axes up to yaw)."""
        hip, ankle = q[6:10], q[10:14]
        reach = c["upper"] + c["lower"] * jnp.cos(ankle)
        ang = phi + hip + q[5]
        px, py = reach * jnp.cos(ang), reach * jnp.sin(ang)
        pz = -c["lower"] * jnp.sin(ankle) + py * q[3] - px * q[4]
        return px, py, pz, reach, ang

    def contact(q, qd):
        """Normal force per foot and the cfrc_ext rows (14, 6)."""
        px, py, pz, reach, ang = feet(q)
        pen = jnp.maximum(-(q[2] + pz), 0.0)
        fn = jnp.where(pen > 0, jnp.maximum(
            c["contact_k"] * pen - c["contact_d"] * qd[2], 0.0), 0.0)
        # grounded feet drive the torso against their hip swing
        sweep = qd[6:10] * reach
        dvx = sweep * jnp.sin(ang) - qd[0]
        dvy = -sweep * jnp.cos(ang) - qd[1]
        share = jnp.minimum(dt * c["friction"] * fn / c["mass"], 0.25)
        fx = share * dvx * c["mass"] / dt
        fy = share * dvy * c["mass"] / dt
        wrench = jnp.stack([py * fn - pz * fy, pz * fx - px * fn,
                            px * fy - py * fx, fx, fy, fn], -1)
        # bodies: world, torso, then (hip, thigh, foot) per leg
        rows = jnp.zeros((14, 6)).at[jnp.asarray([4, 7, 10, 13])].set(wrench)
        return fn, share, dvx, dvy, px, py, reach, rows

    def substep(q, qd, a):
        fn, share, dvx, dvy, px, py, _, _ = contact(q, qd)
        load = jnp.concatenate(
            [jnp.zeros(4), fn * c["lower"] * jnp.cos(q[10:14])])
        jd = qd[6:] + dt * (c["gear"] * a - c["damping"] * qd[6:]
                            - load) / c["inertia"]
        j = q[6:] + dt * jd
        stop = (j < lo) | (j > hi)
        j, jd = jnp.clip(j, lo, hi), jnp.where(stop, 0.0, jd)
        vx = qd[0] + jnp.sum(share * dvx)
        vy = qd[1] + jnp.sum(share * dvy)
        vz = qd[2] + dt * (jnp.sum(fn) / c["mass"] - c["gravity"])
        wr = qd[3] + dt * (jnp.sum(fn * py) / c["tilt_inertia"]
                           - c["tilt_damping"] * qd[3])
        wp = qd[4] + dt * (-jnp.sum(fn * px) / c["tilt_inertia"]
                           - c["tilt_damping"] * qd[4])
        wy = qd[5] + jnp.sum(share * (-c["yaw_gain"] * qd[6:10] - qd[5]))
        v = jnp.stack([vx, vy, vz, wr, wp, wy])
        base = q[:6] + dt * v
        base = base.at[3:5].set(jnp.clip(base[3:5], -1.0, 1.0))
        return (jnp.concatenate([base, j]), jnp.concatenate([v, jd]))

    def quat(r, p, y):
        cr, sr = jnp.cos(0.5 * r), jnp.sin(0.5 * r)
        cp, sp = jnp.cos(0.5 * p), jnp.sin(0.5 * p)
        cy, sy = jnp.cos(0.5 * y), jnp.sin(0.5 * y)
        return jnp.stack([cr * cp * cy + sr * sp * sy,
                          sr * cp * cy - cr * sp * sy,
                          cr * sp * cy + sr * cp * sy,
                          cr * cp * sy - sr * sp * cy])

    def obs(s: EnvState):
        cfrc = jnp.clip(contact(s.q, s.qd)[-1], -1.0, 1.0)
        return jnp.concatenate([s.q[2:3], quat(s.q[3], s.q[4], s.q[5]),
                                s.q[6:], s.qd, cfrc.reshape(-1)])

    def reset(key):
        k1, k2, k3 = jax.random.split(key, 3)
        q0 = jnp.asarray([0.0, 0.0, c["z0"], 0.0, 0.0, 0.0]
                         + [0.0] * 4 + [c["ankle0"]] * 4)
        q = q0 + jax.random.uniform(k1, (14,), minval=-0.1, maxval=0.1)
        return _mk_state(k3, q, 0.1 * jax.random.normal(k2, (14,)))

    def step(s: EnvState, a: jax.Array):
        a = jnp.clip(a, -1, 1)
        q, qd = s.q, s.qd
        for _ in range(c["substeps"]):
            q, qd = substep(q, qd, a)
        ns = EnvState(q=q, qd=qd, t=s.t + 1, key=s.key)
        cfrc = jnp.clip(contact(q, qd)[-1], -1.0, 1.0)
        healthy = (q[2] >= c["healthy_z"][0]) & (q[2] <= c["healthy_z"][1])
        reward = ((q[0] - s.q[0]) / (c["substeps"] * dt)
                  - 0.5 * jnp.sum(jnp.square(a))
                  - 5e-4 * jnp.sum(jnp.square(cfrc))
                  + healthy.astype(jnp.float32))
        return ns, obs(ns), reward, jnp.bool_(False)

    return EnvSpec("quadruped", 111, 8, c["episode"], reset, step, obs)


ENVS: Dict[str, Callable[[], EnvSpec]] = {
    "pendulum": make_pendulum,
    "cartpole_swingup": make_cartpole_swingup,
    "reacher2": make_reacher2,
    "pointmass": make_pointmass,
    "acrobot": make_acrobot,
    "quadruped": make_quadruped,
}


def make_env(name: str) -> EnvSpec:
    return ENVS[name]()


def rollout_return(env: EnvSpec, policy, key: jax.Array,
                   steps: int = 0) -> jax.Array:
    """Deterministic-policy episode return (jitted evaluation loop).

    ``policy`` is a ``repro.rl.Policy`` handle (its ``act_deterministic``
    is used) or a bare ``obs -> action`` callable.
    """
    steps = steps or env.max_episode_steps
    s = env.reset(key)
    act = getattr(policy, "act_deterministic", policy)

    def body(carry, _):
        s, total = carry
        a = act(env.obs(s))
        s, _, r, _ = env.step(s, a)
        return (s, total + r), None

    (_, total), _ = jax.lax.scan(body, (s, jnp.float32(0.0)), None,
                                 length=steps)
    return total


def eval_returns(env: EnvSpec, policy, key: jax.Array,
                 episodes: int) -> jax.Array:
    """Per-episode deterministic-policy returns as ONE traceable program.

    ``policy`` is a params-bound ``repro.rl.Policy`` (eval is just another
    policy client). All ``episodes`` rollouts run as a vmapped
    ``lax.scan``, so a whole evaluation point costs a single host
    dispatch — and the scanned training superstep can fold it into the
    same jitted chunk. Episode keys are ``fold_in(key, i)``, matching the
    legacy per-episode loop; a single observation batches through the
    network exactly as before (``obs[None] -> action[0]``, inside
    ``Policy.act_deterministic``).
    """
    def one(i):
        return rollout_return(env, policy, jax.random.fold_in(key, i))

    return jax.vmap(one)(jnp.arange(episodes))
