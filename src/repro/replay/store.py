"""Pure-JAX circular transition store + per-actor n-step rollback buffer.

The device-resident mirror of the host buffers' ``data`` dict: a pytree of
preallocated ``(capacity, ...)`` arrays plus int32 write cursor and live
count. All operations are pure functions (old state in, new state out) so the
whole Ape-X ``add -> sample -> update`` loop jits into one device program.
``store_add`` writes the appended block as contiguous rows: under jit it
lowers to in-place dynamic-update-slices, with no reallocation and no host
round-trip. A scatter of the same rows would make XLA:TPU carry a column one
element wide (``act`` when ``act_dim`` is 1) lane-padded, 128x its size,
and relayout the whole column for the sample's gather on every add; the
slices keep every column in a compact layout.

``nstep_init``/``nstep_push``/``nstep_push_seq`` implement the Ape-X n-step
return (Horgan et al. 2018, n=3 default) as a small per-actor rollback ring
sitting in front of the store: each incoming 1-step transition displaces the
transition from n-1 steps ago, emitted with the discounted reward sum over
its window and a ``disc`` bootstrap coefficient (gamma^span * (1-done),
truncated at episode boundaries). Everything is pure jnp, so the n-step
computation fuses into the same device program as the replay add.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Store = Dict[str, jax.Array]   # {"data": {...}, "ptr": i32, "count": i32}

# per-actor ring fields mirrored from the collectors' transition dicts
_NSTEP_FIELDS = ("obs", "act", "rew", "next_obs", "done", "boundary")


def store_init(capacity: int, obs_dim: int, act_dim: int,
               dtype=jnp.float32, extra_fields: Tuple[str, ...] = ()) -> Store:
    c = int(capacity)
    data = {
        "obs": jnp.zeros((c, obs_dim), dtype),
        "act": jnp.zeros((c, act_dim), dtype),
        "rew": jnp.zeros((c,), dtype),
        "next_obs": jnp.zeros((c, obs_dim), dtype),
        "done": jnp.zeros((c,), dtype),
    }
    for f in extra_fields:          # scalar-per-row extras (e.g. n-step disc)
        data[f] = jnp.zeros((c,), dtype)
    return {"data": data, "ptr": jnp.zeros((), jnp.int32),
            "count": jnp.zeros((), jnp.int32)}


def store_capacity(store: Store) -> int:
    return store["data"]["rew"].shape[0]


def _write_block(v: jax.Array, rows: jax.Array, ptr: jax.Array) -> jax.Array:
    """Write the ``n <= capacity`` ``rows`` to rows ``ptr .. ptr+n`` of
    ``v``, wrapping past the end, as two dynamic-update-slices.

    The block is rolled so that it lands whole at ``min(ptr, cap - n)``;
    when it crosses the end, its first ``shift`` rows there belong to older
    transitions and keep them, and the same rows of the rolled block are the
    ones that wrap to rows ``0 .. shift``. ``shift`` is at most n - 1, so
    only n - 1 old rows are read back: a one-row block reads nothing.
    """
    cap, n = v.shape[0], rows.shape[0]
    start = jnp.minimum(ptr, cap - n)
    shift = ptr - start
    blk = jnp.roll(rows.astype(v.dtype), shift, axis=0)
    wrap = (jnp.arange(n - 1) < shift).reshape((n - 1,) + (1,) * (v.ndim - 1))
    older = jax.lax.dynamic_slice_in_dim(v, start, n - 1)
    v = jax.lax.dynamic_update_slice_in_dim(
        v, jnp.concatenate([jnp.where(wrap, older, blk[:-1]), blk[-1:]]),
        start, 0)
    return jax.lax.dynamic_update_slice_in_dim(
        v, jnp.where(wrap, blk[:-1], v[:n - 1]), 0, 0)


def store_add(store: Store, batch: Dict[str, jax.Array]
              ) -> tuple[Store, jax.Array]:
    """Append a transition batch at the cursor (wrapping); returns the
    (new_store, written row indices)."""
    cap = store_capacity(store)
    n = batch["obs"].shape[0]
    ptr = store["ptr"]
    if n > cap:
        # a batch that laps the buffer keeps only its last `cap` rows, the
        # host buffer's sequential last-write-wins outcome
        batch = {k: v[-cap:] for k, v in batch.items()}
        ptr = (ptr + (n - cap)) % cap
    idx = (ptr + jnp.arange(min(n, cap), dtype=jnp.int32)) % cap
    data = {k: _write_block(v, batch[k], ptr)
            for k, v in store["data"].items()}
    return {
        "data": data,
        "ptr": ((store["ptr"] + n) % cap).astype(jnp.int32),
        "count": jnp.minimum(store["count"] + n, cap).astype(jnp.int32),
    }, idx


def store_gather(store: Store, idx: jax.Array) -> Dict[str, jax.Array]:
    return {k: v[idx] for k, v in store["data"].items()}


# --------------------------------------------------------------------------
# n-step rollback buffer (Ape-X n-step returns, computed in the add path)
# --------------------------------------------------------------------------

def nstep_init(n: int, n_actors: int, obs_dim: int, act_dim: int,
               dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Ring holding each actor's ``n`` most recent 1-step transitions."""
    shapes = {"obs": (obs_dim,), "act": (act_dim,), "rew": (),
              "next_obs": (obs_dim,), "done": (), "boundary": ()}
    buf = {k: jnp.zeros((int(n), int(n_actors)) + s, dtype)
           for k, s in shapes.items()}
    buf["t"] = jnp.zeros((), jnp.int32)          # total pushes so far
    return buf


def nstep_push(n: int, gamma: float, buf: Dict[str, jax.Array],
               tr: Dict[str, jax.Array]
               ) -> tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Push one env step per actor; emit the transition from n-1 steps ago.

    ``tr`` fields are ``(n_actors, ...)``. The emitted batch carries the
    n-step reward sum and ``disc = gamma^span * (1 - done)`` where the window
    truncates at the first episode ``boundary`` (reward of the boundary step
    included, bootstrap from its ``next_obs``). Emissions are only valid once
    the ring is primed — the first n-1 pushes (``buf["t"] < n-1``) must be
    dropped by the caller (statically: the runner primes during warmup).
    """
    t = buf["t"]
    slot = t % n
    out = {k: buf[k].at[slot].set(tr[k].astype(buf[k].dtype))
           for k in _NSTEP_FIELDS}
    out["t"] = t + 1
    # window oldest-first: ring[(slot + 1 + j) % n], j = 0 .. n-1
    win = {k: [out[k][(slot + 1 + j) % n] for j in range(n)]
           for k in _NSTEP_FIELDS}
    alive = jnp.ones_like(win["rew"][0])         # no boundary before step j
    rew = jnp.zeros_like(win["rew"][0])
    next_obs = jnp.zeros_like(win["next_obs"][0])
    done = jnp.zeros_like(win["done"][0])
    disc = jnp.zeros_like(win["done"][0])
    for j in range(n):
        rew = rew + (gamma ** j) * alive * win["rew"][j]
        # one-hot selector for the last step of the window: the first
        # boundary, or step n-1 when the window is boundary-free
        last = alive * (win["boundary"][j] if j < n - 1
                        else jnp.ones_like(alive))
        next_obs = next_obs + last[:, None] * win["next_obs"][j]
        done = done + last * win["done"][j]
        disc = disc + last * (gamma ** (j + 1)) * (1.0 - win["done"][j])
        alive = alive * (1.0 - win["boundary"][j])
    emitted = {"obs": win["obs"][0], "act": win["act"][0], "rew": rew,
               "next_obs": next_obs, "done": done, "disc": disc}
    return out, emitted


def nstep_push_seq(n: int, gamma: float, buf: Dict[str, jax.Array],
                   trs: Dict[str, jax.Array]
                   ) -> tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Scan ``nstep_push`` over a ``(steps, n_actors, ...)`` sequence;
    emitted fields come back ``(steps, n_actors, ...)`` in push order."""
    def step(b, tr):
        return nstep_push(n, gamma, b, tr)

    return jax.lax.scan(step, buf, {k: trs[k] for k in _NSTEP_FIELDS})


def nstep_emit_flat(n: int, gamma: float, buf: Dict[str, jax.Array],
                    trs: Dict[str, jax.Array], steps: int, drop: int = 0
                    ) -> tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Roll a collector's FLAT ``(steps * n_actors, ...)`` transition batch
    through the ring and return store-schema rows, flat again.

    The single transform shared by the single-shard and sharded add paths:
    unflatten steps-major, push sequentially, statically ``drop`` the first
    unprimed emissions (warmup), re-flatten.
    """
    seq = jax.tree_util.tree_map(
        lambda x: x.reshape((steps, -1) + x.shape[1:]), trs)
    buf, emitted = nstep_push_seq(n, gamma, buf, seq)
    emitted = jax.tree_util.tree_map(lambda x: x[drop:], emitted)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), emitted)
    return buf, flat
