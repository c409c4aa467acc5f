"""Shared utilities: parameter init, activation registry, pytree helpers.

The framework is pure-JAX (no flax): every module is an (init, apply) pair
over plain dict pytrees. ``Dense`` params are ``{"w": (in, out), "b": (out,)}``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, Iterable, Sequence

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
PRNGKey = jax.Array


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def swish(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


ACTIVATIONS: Dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": jax.nn.relu,
    "swish": swish,
    "silu": swish,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "identity": lambda x: x,
}


def get_activation(name: str) -> Callable[[jax.Array], jax.Array]:
    try:
        return ACTIVATIONS[name]
    except KeyError as e:  # pragma: no cover - config error path
        raise ValueError(f"unknown activation {name!r}; have {sorted(ACTIVATIONS)}") from e


# ---------------------------------------------------------------------------
# initializers / dense layers
# ---------------------------------------------------------------------------

def uniform_fan_in(key: PRNGKey, fan_in: int, shape: Sequence[int],
                   dtype=jnp.float32) -> jax.Array:
    """Torch-style U(-1/sqrt(fan_in), 1/sqrt(fan_in)) used by the paper's codebase."""
    bound = 1.0 / math.sqrt(max(fan_in, 1))
    return jax.random.uniform(key, tuple(shape), dtype, -bound, bound)


def dense_init(key: PRNGKey, in_dim: int, out_dim: int, *, bias: bool = True,
               scale: float | None = None, dtype=jnp.float32) -> Params:
    wkey, bkey = jax.random.split(key)
    if scale is None:
        w = uniform_fan_in(wkey, in_dim, (in_dim, out_dim), dtype)
    else:
        w = jax.random.normal(wkey, (in_dim, out_dim), dtype) * scale
    p: Params = {"w": w}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense_apply(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------

def tree_size(tree: Any) -> int:
    """Total number of parameters in a pytree."""
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def tree_l2_norm(tree: Any) -> jax.Array:
    """Global L2 norm over every leaf of a pytree (grad/update diagnostics)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))


def tree_update_ratio(new: Any, old: Any, eps: float = 1e-12) -> jax.Array:
    """||new - old|| / ||old||: the per-step relative parameter movement.

    The classic network-health signal — a healthy run sits around 1e-3/1e-4;
    spikes flag exploding updates, a collapse to ~0 flags dead optimization.
    """
    delta = jax.tree_util.tree_map(lambda a, b: a - b, new, old)
    return tree_l2_norm(delta) / (tree_l2_norm(old) + eps)


def ema_update(target: Any, online: Any, tau: float) -> Any:
    """Polyak averaging: target <- tau*online + (1-tau)*target (paper A.1)."""
    return jax.tree_util.tree_map(lambda t, o: (1.0 - tau) * t + tau * o, target, online)


def split_keys(key: PRNGKey, names: Iterable[str]) -> Dict[str, PRNGKey]:
    names = list(names)
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


def cast_tree(tree: Any, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def huber(x: jax.Array, delta: float = 1.0) -> jax.Array:
    """Elementwise Huber loss on residuals (paper A.1 uses it for Q-regression)."""
    a = jnp.abs(x)
    return jnp.where(a <= delta, 0.5 * x * x, delta * (a - 0.5 * delta))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication (vma) checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
