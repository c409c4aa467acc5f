"""Plain reference for the SAC training superstep.

Written from the published descriptions, in straightforward ``jax.numpy``
and NumPy, importing nothing of the program under test:

* pendulum swing-up dynamics (the classic-control equations, dt = 0.05 s);
* the MLP / MLP-DenseNet trunk (Ota et al. 2021, §3.3) and OFENet
  (Ota et al. 2020): ``y_i = f_i([y_0, ..., y_{i-1}])``;
* SAC with twin critics, Huber critic loss, automatic temperature and
  Polyak target critics (Haarnoja et al. 2018), the OFENet auxiliary
  next-state loss with its Polyak target, and Adam;
* prioritized replay (Schaul et al. 2016): a circular store, proportional
  stratified sampling with ``(|td| + eps) ** alpha`` priorities and
  max-normalized ``(N p) ** -beta`` importance weights. Sampling here is a
  float64 prefix sum on the host, not a tree.

The PRNG schedule (which key draws which noise) is the configuration's
documented one, so that the same seed gives the same draws as the system.
Every function takes the compute dtype: float32 under
``jax.default_matmul_precision("highest")`` is the reference; the
lower-precision control is bfloat16 throughout.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

FIELDS = ("obs", "act", "rew", "next_obs", "done")

# --------------------------------------------------------------- pendulum
G, M, L, DT, MAX_SPEED, MAX_TORQUE, T_LIMIT = 10.0, 1.0, 1.0, 0.05, 8.0, 2.0, 200


def pend_reset(key):
    k1, k2, k3 = jax.random.split(key, 3)
    th = jax.random.uniform(k1, (), minval=-jnp.pi, maxval=jnp.pi)
    thd = jax.random.uniform(k2, (), minval=-1.0, maxval=1.0)
    return {"th": th, "thd": thd, "t": jnp.int32(0), "key": k3}


def pend_obs(s):
    return jnp.stack([jnp.cos(s["th"]), jnp.sin(s["th"]),
                      s["thd"] / MAX_SPEED], -1)


def pend_step(s, a):
    u = jnp.clip(a[..., 0], -1, 1) * MAX_TORQUE
    th, thd = s["th"], s["thd"]
    norm_th = jnp.mod(th + jnp.pi, 2 * jnp.pi) - jnp.pi
    cost = norm_th ** 2 + 0.1 * thd ** 2 + 0.001 * u ** 2
    thd = jnp.clip(thd + (3 * G / (2 * L) * jnp.sin(th)
                          + 3.0 / (M * L ** 2) * u) * DT,
                   -MAX_SPEED, MAX_SPEED)
    ns = {"th": th + thd * DT, "thd": thd, "t": s["t"] + 1, "key": s["key"]}
    return ns, -cost


def env_collect(states, act_fn, key, n):
    """One env step for ``n`` actors: actions from ``act_fn(obs, key)``,
    time-limit resets from ``split(key, n)``. Returns (states, rows)."""
    obs = pend_obs(states)
    acts = act_fn(obs, key)
    st2, rew = jax.vmap(pend_step)(states, acts)
    obs2 = pend_obs(st2)
    fresh = jax.vmap(pend_reset)(jax.random.split(key, n))
    reset = st2["t"] >= T_LIMIT
    st3 = jax.tree_util.tree_map(
        lambda a, b: jnp.where(reset.reshape((-1,) + (1,) * (a.ndim - 1)),
                               b, a), st2, fresh)
    rows = {"obs": obs, "act": acts.astype(jnp.float32),
            "rew": rew.astype(jnp.float32), "next_obs": obs2,
            "done": jnp.zeros((n,), jnp.float32)}
    return st3, rows


# ------------------------------------------------------------- networks
def _act(name):
    if name == "swish":
        return lambda x: x * jax.nn.sigmoid(x)
    if name == "relu":
        return lambda x: jnp.maximum(x, 0)
    raise ValueError(name)


def block(p, x, connectivity: str, activation: str):
    """Hidden stack (+ output layer when ``p`` has ``out``): returns
    (output, feature)."""
    f = _act(activation)
    stream = h = x
    for layer in p["layers"]:
        inp = stream if connectivity == "densenet" else h
        h = f(inp @ layer["dense"]["w"] + layer["dense"]["b"])
        if connectivity == "densenet":
            stream = jnp.concatenate([stream, h], -1)
    feat = stream if connectivity == "densenet" else h
    out = feat @ p["out"]["w"] + p["out"]["b"] if "out" in p else feat
    return out, feat


class Nets:
    """The networks of one configuration (sizes come from its file)."""

    def __init__(self, cfg: dict):
        s = cfg["spec"]
        self.net = s["network"]
        self.ofe = s["ofenet"] if s["ofenet"]["enabled"] else None
        self.hp = cfg["hyperparameters"]
        self.act_dim = cfg["env_dims"]["act"]

    def z_s(self, p, s):
        if self.ofe is None:
            return s
        return block(p["ofenet"]["online"]["phi_s"], s,
                     self.ofe["connectivity"], self.ofe["activation"])[1]

    def z_sa(self, p, s, a, which="online"):
        if self.ofe is None:
            return jnp.concatenate([s, a], -1)
        o = p["ofenet"][which]
        zs = block(o["phi_s"], s, self.ofe["connectivity"],
                   self.ofe["activation"])[1]
        return block(o["phi_sa"], jnp.concatenate([zs, a], -1),
                     self.ofe["connectivity"], self.ofe["activation"])[1]

    def dist(self, actor, zs):
        out, _ = block(actor, zs, self.net["connectivity"],
                       self.net["activation"])
        mu, log_std = out[..., :self.act_dim], out[..., self.act_dim:]
        return mu, jnp.clip(log_std, self.hp["log_std_min"],
                            self.hp["log_std_max"])

    def sample(self, p, s, key):
        mu, log_std = self.dist(p["actor"], self.z_s(p, s))
        eps = jax.random.normal(key, mu.shape).astype(mu.dtype)
        a = jnp.tanh(mu + jnp.exp(log_std) * eps)
        logp = jnp.sum(-0.5 * (eps ** 2 + 2 * log_std + math.log(2 * math.pi))
                       - jnp.log(jnp.maximum(1 - a ** 2, 1e-6)), -1)
        return a, logp

    def q(self, critics, zsa):
        c, a = self.net["connectivity"], self.net["activation"]
        return (block(critics["q1"], zsa, c, a)[0][..., 0],
                block(critics["q2"], zsa, c, a)[0][..., 0])


def adam(g, m, v, p, count, lr, b1, b2, eps):
    c = count.astype(jnp.float32) + 1
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ ** 2,
                               v, g)
    p = jax.tree_util.tree_map(
        lambda p_, m_, v_: p_ - (lr * (m_ / (1 - b1 ** c))
                                 / (jnp.sqrt(v_ / (1 - b2 ** c)) + eps)
                                 ).astype(p_.dtype), p, m, v)
    return p, m, v


def _huber(x):
    a = jnp.abs(x)
    return jnp.where(a <= 1.0, 0.5 * x * x, a - 0.5)


def _ema(t, o, tau):
    return jax.tree_util.tree_map(lambda a, b: (1 - tau) * a + tau * b, t, o)


def sac_step(nets: Nets, params, opt, batch, key):
    """One SAC (+ OFENet) update. ``opt[name] = (m, v, count)``.
    Returns (params, opt, metrics, grads)."""
    hp = nets.hp
    ad = dict(lr=hp["lr"], b1=hp["adam_b1"], b2=hp["adam_b2"],
              eps=hp["adam_eps"])
    s, a, r, s2, d, w = (batch["obs"], batch["act"], batch["rew"],
                         batch["next_obs"], batch["done"], batch["weight"])
    k1, k2 = jax.random.split(key)
    p, o, grads, metrics = dict(params), dict(opt), {}, {}

    if nets.ofe is not None:
        def aux(online):
            pk = {"ofenet": {"online": online}}
            pred = nets.z_sa(pk, s, a) @ online["pred"]["w"] \
                + online["pred"]["b"]
            return jnp.mean(jnp.sum((pred - s2) ** 2, -1))
        online = params["ofenet"]["online"]
        metrics["aux_loss"], g = jax.value_and_grad(aux)(online)
        m, v, c = opt["ofenet"]
        new_online, m, v = adam(g, m, v, online, c, **ad)
        p["ofenet"] = {"online": new_online,
                       "target": _ema(params["ofenet"]["target"], new_online,
                                      hp["ofenet_tau"])}
        o["ofenet"] = (m, v, c + 1)
        grads["ofenet"] = g

    alpha = jnp.exp(params["log_alpha"])
    a2, logp2 = nets.sample(p, s2, k1)
    zsa2 = nets.z_sa(p, s2, a2)
    q1t, q2t = nets.q(params["target_critics"], zsa2)
    qt = jax.lax.stop_gradient(
        r + hp["gamma"] * (1.0 - d) * (jnp.minimum(q1t, q2t) - alpha * logp2))
    zsa = nets.z_sa(p, s, a)

    def critic_loss(cr):
        q1, q2 = nets.q(cr, zsa)
        return jnp.mean(w * _huber(q1 - qt)) + jnp.mean(w * _huber(q2 - qt))

    metrics["critic_loss"], g = jax.value_and_grad(critic_loss)(
        params["critics"])
    m, v, c = opt["critics"]
    critics, m, v = adam(g, m, v, params["critics"], c, **ad)
    p["critics"], o["critics"], grads["critics"] = critics, (m, v, c + 1), g

    def actor_loss(actor):
        pa = {**p, "actor": actor}
        ai, logp = nets.sample(pa, s, k2)
        q1, q2 = nets.q(critics, nets.z_sa(pa, s, ai))
        return jnp.mean(alpha * logp - jnp.minimum(q1, q2)), logp

    (metrics["actor_loss"], logp), g = jax.value_and_grad(
        actor_loss, has_aux=True)(params["actor"])
    m, v, c = opt["actor"]
    p["actor"], m, v = adam(g, m, v, params["actor"], c, **ad)
    o["actor"], grads["actor"] = (m, v, c + 1), g

    def alpha_loss(la):
        return -jnp.mean(jnp.exp(la) * jax.lax.stop_gradient(
            logp - float(nets.act_dim)))

    g = jax.grad(alpha_loss)(params["log_alpha"])
    m, v, c = opt["alpha"]
    p["log_alpha"], m, v = adam(g, m, v, params["log_alpha"], c, **ad)
    o["alpha"], grads["alpha"] = (m, v, c + 1), g

    p["target_critics"] = _ema(params["target_critics"], critics, hp["tau"])
    q1, _ = nets.q(critics, zsa)
    metrics["priorities"] = jnp.abs(q1 - qt)
    return p, o, metrics, grads


def trained(params) -> Dict[str, object]:
    """The optimizer-owned subtrees, keyed like the optimizer states."""
    out = {"actor": params["actor"], "critics": params["critics"],
           "alpha": params["log_alpha"]}
    if "ofenet" in params:
        out["ofenet"] = params["ofenet"]["online"]
    return out


def opt_init(params):
    z = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    return {k: (z(t), z(t), jnp.zeros((), jnp.int32))
            for k, t in trained(params).items()}


def cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


# ---------------------------------------------------------------- replay
class Replay:
    """Host-side circular store + proportional priorities (float64)."""

    def __init__(self, capacity: int, obs_dim: int, act_dim: int, hp: dict):
        self.cap, self.hp = capacity, hp
        self.data = {"obs": np.zeros((capacity, obs_dim), np.float32),
                     "act": np.zeros((capacity, act_dim), np.float32),
                     "rew": np.zeros((capacity,), np.float32),
                     "next_obs": np.zeros((capacity, obs_dim), np.float32),
                     "done": np.zeros((capacity,), np.float32)}
        self.prio = np.zeros((capacity,), np.float64)
        self.ptr = self.count = 0
        self.max_p = 1.0

    def add(self, rows: Dict[str, np.ndarray]) -> None:
        n = rows["obs"].shape[0]
        idx = (self.ptr + np.arange(n)) % self.cap
        for k in FIELDS:
            self.data[k][idx] = rows[k]
        self.prio[idx] = (self.max_p + self.hp["per_eps"]) ** self.hp["per_alpha"]
        self.ptr = (self.ptr + n) % self.cap
        self.count = min(self.count + n, self.cap)

    def sample(self, u: np.ndarray):
        """Stratified proportional draw from uniforms ``u`` in [0, 1)."""
        b = u.shape[0]
        cum = np.cumsum(self.prio)
        total = cum[-1]
        targets = (np.arange(b) + u.astype(np.float64)) * (total / b)
        idx = np.minimum(np.searchsorted(cum, targets, side="right"),
                         self.count - 1)
        p = self.prio[idx] / total
        w = (self.count * np.maximum(p, 1e-12)) ** (-self.hp["per_beta"])
        batch = {k: v[idx] for k, v in self.data.items()}
        batch["weight"] = (w / w.max()).astype(np.float32)
        return batch, idx

    def update(self, idx: np.ndarray, td: np.ndarray) -> None:
        pr = np.abs(td.astype(np.float64)) + self.hp["per_eps"]
        self.max_p = max(self.max_p, float(pr.max()))
        self.prio[idx] = pr ** self.hp["per_alpha"]


# -------------------------------------------------------------- the run
def follow(cfg: dict, params0, seed: int, steps: int, dtype=jnp.float32
           ) -> List[dict]:
    """Follow the first ``steps`` supersteps from the seed.

    The key schedule: ``key(seed) -> (key, k_init, k_actor)``, actors reset
    from ``split(k_actor, n)``, ``(key, k_warm)``; the warm-up collects
    ``max(warmup // n, 1)`` random-action steps from ``split(k_warm, .)``;
    every superstep splits ``(key, k_collect, k_sample, k_update)``.
    Returns, per step, the metrics, gradients, params, the sampled batch
    and indices, the collected rows and the priority total after it."""
    x, r = cfg["spec"]["execution"], cfg["spec"]["replay"]
    n = x["n_core"] * x["n_env"] if x["distributed"] else 1
    b = x["batch_size"]
    dims = cfg["env_dims"]
    nets = Nets(cfg)
    rp = Replay(r["capacity"], dims["obs"], dims["act"], cfg["hyperparameters"])

    key = jax.random.key(seed)
    key, _k_init, k_actor = jax.random.split(key, 3)
    actors = jax.vmap(pend_reset)(jax.random.split(k_actor, n))
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

    params = cast(params0, dtype)
    opt = opt_init(params)
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

