"""Jit'd dispatch layer for the device sum-tree: Pallas kernel or XLA ref.

``backend="pallas"`` runs the kernels from ``replay_tree.py`` (interpret
mode on CPU): the sample's two-stage descent (the tree's top levels from a
VMEM block, the deeper ones, at most 7, from rows DMA'd by the node ids
the top stage found; the split follows from the tree's depth) and the
one-hot set. ``backend="xla"`` runs the pure jnp oracle from ``ref.py`` —
the same functions the tests use as ground truth, and the sensible
default on CPU where interpret-mode Pallas is slow.
``repro.replay`` calls only through this layer, so the replay subsystem is
backend-agnostic. Asking for ``backend="pallas", interpret=False`` where
Mosaic cannot lower (off-TPU) raises instead of running something else.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import require_mosaic
from repro.kernels.replay_tree import ref
from repro.kernels.replay_tree.replay_tree import (tree_sample, tree_set,
                                                   tree_set_onehot)

BACKENDS = ("xla", "pallas")


def sumtree_init(capacity: int) -> jax.Array:
    """Zeroed flat tree: 2**depth float32 nodes, root at 1."""
    return ref.tree_init_ref(capacity)


def sumtree_total(tree: jax.Array) -> jax.Array:
    return ref.tree_total_ref(tree)


def sumtree_get(tree: jax.Array, idx: jax.Array) -> jax.Array:
    return ref.tree_get_ref(tree, idx)


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def sumtree_set(tree: jax.Array, idx: jax.Array, value: jax.Array, *,
                backend: str = "xla", interpret: bool = True) -> jax.Array:
    """Write ``value`` at leaves ``idx`` and refresh ancestor sums.

    ``backend="pallas"`` under interpret mode runs the scatter+resum kernel
    (scatter does not lower on Mosaic); real-lowering on TPU routes to
    ``tree_set_onehot``, which rewrites the scatter as per-level one-hot
    delta propagation — so on hardware both the sample descent AND the
    priority refresh stay fused Pallas kernels.
    """
    assert backend in BACKENDS, backend
    if backend == "pallas":
        if interpret:
            return tree_set(tree, idx, value, interpret=True)
        require_mosaic("sumtree_set")
        return tree_set_onehot(tree, idx, value, interpret=False)
    return ref.tree_set_ref(tree, idx, value)


@functools.partial(jax.jit,
                   static_argnames=("capacity", "backend", "bt", "interpret"))
def sumtree_sample(tree: jax.Array, targets: jax.Array, *, capacity: int,
                   backend: str = "xla", bt: int = 128,
                   interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Batch proportional descent -> (leaf_idx, leaf_priority).

    The Pallas descent returns the oracle's leaves and stored priorities
    bit for bit. Targets are padded up to a multiple of the kernel's batch
    tile ``bt``; the pad lanes descend with target 0 and are sliced off.
    """
    assert backend in BACKENDS, backend
    (b,) = targets.shape
    if backend == "pallas":
        if not interpret:
            require_mosaic("sumtree_sample")
        pad = (-b) % bt
        tp = jnp.pad(targets, (0, pad)) if pad else targets
        leaf, pri = tree_sample(tree, tp, capacity=capacity, bt=bt,
                                interpret=interpret)
        return leaf[:b], pri[:b]
    leaf = ref.tree_sample_ref(tree, targets, capacity=capacity)
    return leaf, ref.tree_get_ref(tree, leaf)
