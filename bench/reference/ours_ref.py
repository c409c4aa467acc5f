"""Plain reference for the paper's agent: SAC on OFENet features with
batch norm, an Ape-X actor pool, on the quadruped env.

Written from the published descriptions in straightforward ``jax.numpy``,
importing nothing of the program under test. It reuses ``sac_ref`` for
what is the same there (SAC with twin critics, Huber critic loss,
automatic temperature and Polyak target critics; Adam; the prioritized
replay) and adds:

* the quadruped env (a torso on four two-joint legs at Ant-v2's widths:
  111 observation and 8 action dims, dt 5 x 0.01 s, Ant-v2's reward terms,
  1 000-step episodes), its own copy of the equations;
* OFENet (Ota et al. 2020) with batch norm after each dense layer, before
  its activation: the auxiliary next-state step normalizes by the batch's
  statistics and moves the running ones by momentum; acting, the critic
  targets and losses and the actor loss read the features normalized by
  the running statistics. Where the paper leaves it open this follows the
  program's documented choice: momentum 0.99, eps 1e-5, the biased batch
  variance ``E[x^2] - E[x]^2``, the running statistics taking the step's
  batch statistics after the Adam step of the other OFENet weights (whose
  gradient they do not get), and the target network a Polyak average of
  every online leaf, the statistics included;
* ``follow``, the run from the seed with the configuration's documented
  key schedule (``sac_ref.follow``'s, on this env and actor pool).

Float32 under ``jax.default_matmul_precision("highest")`` is the
reference; the lower-precision control is the same code in bfloat16.
"""
from __future__ import annotations

from typing import List

import numpy as np

import jax
import jax.numpy as jnp

from reference import sac_ref

# ------------------------------------------------------------- quadruped
SUBSTEPS, DT = 5, 0.01
GEAR, INERTIA, DAMPING = 150.0, 20.0, 10.0
HIP, ANKLE_LO, ANKLE_HI = 0.52, 0.52, 1.22
LEG_ANGLES = (0.25, 0.75, 1.25, 1.75)          # x pi
UPPER, LOWER = 0.28, 0.57
MASS, GRAVITY, TILT_INERTIA, TILT_DAMPING = 1.0, 9.81, 0.5, 2.0
CONTACT_K, CONTACT_D, FRICTION, YAW_GAIN = 400.0, 10.0, 1.0, 0.5
Z0, ANKLE0, HEALTHY, T_LIMIT = 0.75, 1.0, (0.2, 1.0), 1000
FEET = (4, 7, 10, 13)          # the feet's rows of the 14 bodies' cfrc_ext


def quad_reset(key):
    k1, k2, k3 = jax.random.split(key, 3)
    q0 = jnp.asarray([0.0, 0.0, Z0, 0.0, 0.0, 0.0] + [0.0] * 4
                     + [ANKLE0] * 4)
    return {"q": q0 + jax.random.uniform(k1, (14,), minval=-0.1,
                                         maxval=0.1),
            "qd": 0.1 * jax.random.normal(k2, (14,)),
            "t": jnp.int32(0), "key": k3}


def _contact(q, qd):
    """Per foot: normal force, friction share, velocity gap (x, y), offset
    (x, y); and the 14 x 6 external-force rows."""
    phi = jnp.pi * jnp.asarray(LEG_ANGLES, jnp.float32)
    hip, ankle = q[6:10], q[10:14]
    reach = UPPER + LOWER * jnp.cos(ankle)
    ang = phi + hip + q[5]
    px, py = reach * jnp.cos(ang), reach * jnp.sin(ang)
    pz = -LOWER * jnp.sin(ankle) + py * q[3] - px * q[4]
    pen = jnp.maximum(-(q[2] + pz), 0.0)
    fn = jnp.where(pen > 0, jnp.maximum(CONTACT_K * pen - CONTACT_D * qd[2],
                                        0.0), 0.0)
    sweep = qd[6:10] * reach
    dvx = sweep * jnp.sin(ang) - qd[0]
    dvy = -sweep * jnp.cos(ang) - qd[1]
    share = jnp.minimum(DT * FRICTION * fn / MASS, 0.25)
    fx, fy = share * dvx * MASS / DT, share * dvy * MASS / DT
    torque_force = jnp.stack([py * fn - pz * fy, pz * fx - px * fn,
                              px * fy - py * fx, fx, fy, fn], -1)
    rows = jnp.zeros((14, 6)).at[jnp.asarray(FEET)].set(torque_force)
    return fn, share, dvx, dvy, px, py, rows


def _substep(q, qd, a):
    lo = jnp.asarray([-HIP] * 4 + [ANKLE_LO] * 4)
    hi = jnp.asarray([HIP] * 4 + [ANKLE_HI] * 4)
    fn, share, dvx, dvy, px, py, _ = _contact(q, qd)
    load = jnp.concatenate([jnp.zeros(4), fn * LOWER * jnp.cos(q[10:14])])
    jd = qd[6:] + DT * (GEAR * a - DAMPING * qd[6:] - load) / INERTIA
    j = q[6:] + DT * jd
    stop = (j < lo) | (j > hi)
    j, jd = jnp.clip(j, lo, hi), jnp.where(stop, 0.0, jd)
    v = jnp.stack([
        qd[0] + jnp.sum(share * dvx),
        qd[1] + jnp.sum(share * dvy),
        qd[2] + DT * (jnp.sum(fn) / MASS - GRAVITY),
        qd[3] + DT * (jnp.sum(fn * py) / TILT_INERTIA
                      - TILT_DAMPING * qd[3]),
        qd[4] + DT * (-jnp.sum(fn * px) / TILT_INERTIA
                      - TILT_DAMPING * qd[4]),
        qd[5] + jnp.sum(share * (-YAW_GAIN * qd[6:10] - qd[5]))])
    base = q[:6] + DT * v
    base = base.at[3:5].set(jnp.clip(base[3:5], -1.0, 1.0))
    return jnp.concatenate([base, j]), jnp.concatenate([v, jd])


def _quat(r, p, y):
    cr, sr = jnp.cos(0.5 * r), jnp.sin(0.5 * r)
    cp, sp = jnp.cos(0.5 * p), jnp.sin(0.5 * p)
    cy, sy = jnp.cos(0.5 * y), jnp.sin(0.5 * y)
    return jnp.stack([cr * cp * cy + sr * sp * sy, sr * cp * cy - cr * sp * sy,
                      cr * sp * cy + sr * cp * sy, cr * cp * sy - sr * sp * cy])


def quad_obs(s):
    cfrc = jnp.clip(_contact(s["q"], s["qd"])[-1], -1.0, 1.0)
    q = s["q"]
    return jnp.concatenate([q[2:3], _quat(q[3], q[4], q[5]), q[6:], s["qd"],
                            cfrc.reshape(-1)])


def quad_step(s, a):
    a = jnp.clip(a, -1, 1)
    q, qd = s["q"], s["qd"]
    for _ in range(SUBSTEPS):
        q, qd = _substep(q, qd, a)
    ns = {"q": q, "qd": qd, "t": s["t"] + 1, "key": s["key"]}
    cfrc = jnp.clip(_contact(q, qd)[-1], -1.0, 1.0)
    healthy = (q[2] >= HEALTHY[0]) & (q[2] <= HEALTHY[1])
    return ns, ((q[0] - s["q"][0]) / (SUBSTEPS * DT)
                - 0.5 * jnp.sum(jnp.square(a))
                - 5e-4 * jnp.sum(jnp.square(cfrc))
                + healthy.astype(jnp.float32))


def env_collect(states, act_fn, key, n):
    """One env step for ``n`` actors (``sac_ref.env_collect`` on this env):
    actions from ``act_fn(obs, key)``, time-limit resets from
    ``split(key, n)``. Returns (states, rows)."""
    obs = jax.vmap(quad_obs)(states)
    acts = act_fn(obs, key)
    st2, rew = jax.vmap(quad_step)(states, acts)
    obs2 = jax.vmap(quad_obs)(st2)
    fresh = jax.vmap(quad_reset)(jax.random.split(key, n))
    reset = st2["t"] >= T_LIMIT
    st3 = jax.tree_util.tree_map(
        lambda a, b: jnp.where(reset.reshape((-1,) + (1,) * (a.ndim - 1)),
                               b, a), st2, fresh)
    rows = {"obs": obs, "act": acts.astype(jnp.float32),
            "rew": rew.astype(jnp.float32), "next_obs": obs2,
            "done": jnp.zeros((n,), jnp.float32)}
    return st3, rows


# --------------------------------------------------- OFENet with batch norm
BN_MOMENTUM, BN_EPS = 0.99, 1e-5


def bn_block(p, x, activation: str, batch: bool):
    """A DenseNet stack, each layer ``act(bn(x W + b))``: returns (feature,
    the layers' running statistics after this pass)."""
    f = sac_ref._act(activation)
    stream, stats = x, []
    for layer in p["layers"]:
        y = stream @ layer["dense"]["w"] + layer["dense"]["b"]
        bn = layer["bn"]
        if batch:
            mean = jnp.mean(y, 0)
            var = jnp.mean(y * y, 0) - mean * mean
            stats.append({
                "mean": BN_MOMENTUM * bn["mean"] + (1 - BN_MOMENTUM) * mean,
                "var": BN_MOMENTUM * bn["var"] + (1 - BN_MOMENTUM) * var})
        else:
            mean, var = bn["mean"], bn["var"]
            stats.append({"mean": mean, "var": var})
        y = (y - mean) * jax.lax.rsqrt(var + BN_EPS) * bn["scale"] + bn["bias"]
        stream = jnp.concatenate([stream, f(y)], -1)
    return stream, stats


class Nets(sac_ref.Nets):
    """``sac_ref.Nets`` whose features come from OFENet with batch norm at
    its running statistics. ``sac_ref.sac_step`` sees no OFENet of its own
    (``ofe`` None) and so takes no auxiliary step: ``sac_step`` below does."""

    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.bn, self.ofe = self.ofe, None
        if not (self.bn["batch_norm"] and self.bn["connectivity"]
                == "densenet"):
            raise ValueError("ours_ref follows OFENet with batch norm and "
                             "DenseNet connectivity only")

    def feats(self, online, s, a, batch=False):
        act = self.bn["activation"]
        zs, st_s = bn_block(online["phi_s"], s, act, batch)
        if a is None:
            return zs, None, (st_s, None)
        zsa, st_sa = bn_block(online["phi_sa"], jnp.concatenate([zs, a], -1),
                              act, batch)
        return zs, zsa, (st_s, st_sa)

    def z_s(self, p, s):
        return self.feats(p["ofenet"]["online"], s, None)[0]

    def z_sa(self, p, s, a, which="online"):
        return self.feats(p["ofenet"][which], s, a)[1]


def _with_stats(net, stats):
    def block(b, st):
        return {**b, "layers": [{**l, "bn": {**l["bn"], **s}}
                                for l, s in zip(b["layers"], st)]}
    return {**net, "phi_s": block(net["phi_s"], stats[0]),
            "phi_sa": block(net["phi_sa"], stats[1])}


def sac_step(nets: Nets, params, opt, batch, key):
    """OFENet's auxiliary step (batch statistics; the running ones moved),
    then ``sac_ref.sac_step`` on the refreshed features."""
    hp = nets.hp
    s, a, s2 = batch["obs"], batch["act"], batch["next_obs"]

    def aux(online):
        _, zsa, stats = nets.feats(online, s, a, batch=True)
        pred = zsa @ online["pred"]["w"] + online["pred"]["b"]
        return jnp.mean(jnp.sum((pred - s2) ** 2, -1)), stats

    online = params["ofenet"]["online"]
    (aux_loss, stats), g = jax.value_and_grad(aux, has_aux=True)(online)
    m, v, c = opt["ofenet"]
    new_online, m, v = sac_ref.adam(g, m, v, online, c, lr=hp["lr"],
                                    b1=hp["adam_b1"], b2=hp["adam_b2"],
                                    eps=hp["adam_eps"])
    new_online = _with_stats(new_online, stats)
    params = dict(params, ofenet={
        "online": new_online,
        "target": sac_ref._ema(params["ofenet"]["target"], new_online,
                               hp["ofenet_tau"])})
    opt = dict(opt, ofenet=(m, v, c + 1))
    p, o, metrics, grads = sac_ref.sac_step(nets, params, opt, batch, key)
    return p, o, dict(metrics, aux_loss=aux_loss), dict(grads, ofenet=g)


# -------------------------------------------------------------- the run
def follow(cfg: dict, params0, seed: int, steps: int, dtype=jnp.float32
           ) -> List[dict]:
    """Follow the first ``steps`` supersteps from the seed, with
    ``sac_ref.follow``'s key schedule: ``key(seed) -> (key, k_init,
    k_actor)``, actors reset from ``split(k_actor, n)``, ``(key, k_warm)``;
    the warm-up collects ``max(warmup // n, 1)`` random-action steps of the
    n actors from ``split(k_warm, .)``; every superstep splits ``(key,
    k_collect, k_sample, k_update)``. Returns what ``sac_ref.follow``
    returns per step."""
    x, r = cfg["spec"]["execution"], cfg["spec"]["replay"]
    n = x["n_core"] * x["n_env"] if x["distributed"] else 1
    b = x["batch_size"]
    dims = cfg["env_dims"]
    nets = Nets(cfg)
    rp = sac_ref.Replay(r["capacity"], dims["obs"], dims["act"],
                        cfg["hyperparameters"])

    key = jax.random.key(seed)
    key, _k_init, k_actor = jax.random.split(key, 3)
    actors = jax.vmap(quad_reset)(jax.random.split(k_actor, n))
    key, kw = jax.random.split(key)
    warm = max(x["warmup_steps"] // n, 1)

    @jax.jit
    def warm_up(actors, kw):
        def rand(obs, k):
            return jax.random.uniform(k, (obs.shape[0], dims["act"]),
                                      minval=-1.0, maxval=1.0)

        def one(st, k):
            return env_collect(st, rand, k, n)
        return jax.lax.scan(one, actors, jax.random.split(kw, warm))

    actors, rows = warm_up(actors, kw)
    rp.add({k: np.asarray(v).reshape((-1,) + v.shape[2:])
            for k, v in rows.items()})

    @jax.jit
    def collect(params, actors, kc):
        k = jax.random.split(kc, 1)[0]
        return env_collect(actors, lambda o, kk: nets.sample(
            params, o.astype(dtype), kk)[0], k, n)

    update = jax.jit(lambda p, o, bt, k: sac_step(nets, p, o, bt, k))

    params = sac_ref.cast(params0, dtype)
    opt = sac_ref.opt_init(params)
    out = []
    for _ in range(steps):
        key, kc, ks, ku = jax.random.split(key, 4)
        actors, rows = collect(params, actors, kc)
        rows = {k: np.asarray(v) for k, v in rows.items()}
        rp.add(rows)
        u = np.asarray(jax.random.uniform(ks, (b,)))
        batch_np, idx = rp.sample(u)
        batch = {k: jnp.asarray(v).astype(dtype) for k, v in batch_np.items()}
        params, opt, metrics, grads = update(params, opt, batch, ku)
        td = np.asarray(metrics["priorities"].astype(jnp.float32))
        rp.update(idx, td)
        out.append({"metrics": {k: float(v) for k, v in metrics.items()
                                if k != "priorities"},
                    "grads": grads, "params": params, "batch": batch_np,
                    "idx": idx, "collected": rows,
                    "prio_total": float(rp.prio.sum())})
    return out
