"""Device meshes for the sharded RL path.

``make_actor_mesh`` builds the one-axis ``data`` mesh that
``execution.mesh_shards`` trains on; ``make_debug_mesh`` a small
``(data, model)`` mesh for tests. Both are functions, so importing this
module never touches device state. Both declare their axes
``AxisType.Auto``: the sharded superstep is written for compiler-propagated
shardings (``shard_map`` bodies plus ``with_sharding_constraint``), and
``jax.make_mesh`` would otherwise default to ``Explicit`` axes, under which
ops such as ``jnp.median`` of a sharded vector refuse to trace.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for in-process tests (requires >= n_data*n_model devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_actor_mesh(n_data: int):
    """Data-only mesh for the RL runner's sharded actor/replay path
    (``ExperimentSpec`` ``execution.mesh_shards=n``): one ``data`` slice
    per replay shard / actor-pool slice, no model axis. Works on real
    devices or a ``--xla_force_host_platform_device_count`` fake CPU mesh."""
    return jax.make_mesh((int(n_data),), ("data",),
                         axis_types=(AxisType.Auto,))


def replay_shards(mesh) -> int:
    """Device-replay shard count: one logical replay shard per ``data`` slice
    (repro.replay.sharded, the Ape-X layout). Total replay capacity is the
    per-shard capacity times this."""
    return int(mesh.shape["data"])
