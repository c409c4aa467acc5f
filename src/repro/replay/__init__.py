"""Device-resident sharded prioritized replay (Ape-X on the mesh).

The training replay: the whole ``collect -> add -> sample ->
update_priorities`` loop stays on device:

* ``store``   — pure-JAX circular transition store: a pytree of
  preallocated ``(capacity, ...)`` arrays plus an int32 cursor; functional
  updates lower to in-place dynamic-update-slice under jit.
* ``device``  — the prioritized replay itself. Sum-tree ops dispatch through
  ``repro.kernels.replay_tree.ops`` to either the fused Pallas descent
  kernel (``backend="pallas"``, interpret mode on CPU) or the XLA
  scatter/gather reference (``backend="xla"``, the CPU-fast default).
  Semantics mirror the NumPy ``rl/replay.py`` buffer (stratified
  proportional sampling, alpha/beta exponents, batch-max-normalized IS
  weights), which stays in-tree as the tests' parity oracle.
* ``sharded`` — one replay shard per mesh ``data``-axis slice, matching the
  sharded actor pool in ``rl/apex.py``: adds are shard-local, sampling is
  stratified across shards, IS weights renormalize via an on-mesh pmax, and
  ``collect_and_add_sharded`` fuses actor stepping with the replay add into
  a single ``shard_map`` program.

Kernel switch: ``ExperimentSpec`` ``replay.kernel = "xla" | "pallas"``.
The runner threads the functional ``ReplayState`` through its superstep —
no per-step host<->device transfer of the replay store (see
examples/rl_distributed.py and benchmarks/replay_micro.py). Because every
operation is pure, the superstep carries the whole ReplayState through
``jax.lax.scan`` — and on a mesh
(``execution.mesh_shards=n``) through ``collect_and_add_sharded`` /
``sharded_replay_sample`` inside the same scanned chunk. ``store.nstep_*``
roll n-step returns (``replay.n_step=3``) on device in the add path;
``ReplayState["add_step"]`` stamps rows for the priority-staleness metric.
"""
from repro.replay.device import (DeviceReplay, DeviceReplayConfig,
                                 ReplayState, replay_add, replay_init,
                                 replay_sample, replay_update)
from repro.replay.sharded import (collect_and_add_sharded,
                                  sharded_nstep_init, sharded_replay_add,
                                  sharded_replay_init, sharded_replay_sample,
                                  sharded_replay_update)
from repro.replay.store import (nstep_emit_flat, nstep_init, nstep_push,
                                nstep_push_seq, store_add, store_gather,
                                store_init)

__all__ = [
    "DeviceReplay", "DeviceReplayConfig", "ReplayState",
    "replay_add", "replay_init", "replay_sample", "replay_update",
    "collect_and_add_sharded", "sharded_nstep_init", "sharded_replay_add",
    "sharded_replay_init", "sharded_replay_sample", "sharded_replay_update",
    "nstep_emit_flat", "nstep_init", "nstep_push", "nstep_push_seq",
    "store_add", "store_gather", "store_init",
]
