"""Pallas sum-tree kernels for device-resident prioritized replay.

The Ape-X hot loop samples a batch of leaves by proportional descent every
learner step.  ``tree_sample`` descends the ``depth - 1`` levels of
``lookup -> compare -> subtract`` for a batch of target masses, gridded
into ``bt``-wide tiles, in two stages, with the tree viewed as
``(rows, 128)``:

* Top stage: levels ``1..s`` from the tree's first ``2**(s + 1)`` nodes,
  one VMEM block.  Mosaic lowers no 1-D gather, so a lookup picks the
  node's row (a one-hot matmul on levels of 128 rows or more, VPU selects
  on narrower ones) and then its lane (a masked lane sum).
* Row stage: levels ``s+1..depth-1`` from the tree left in HBM.  A
  level-``s`` node's descendants ``k <= 7`` levels down share one
  128-lane row, so the stage DMAs each target's row on every level at
  once, from the level-``s`` node ids the top stage wrote, then takes the
  lanes with the same masked lane sums.

``s`` follows from the depth alone (``_split``): the whole descent where
the tree has at most ``_TOP_LEVELS + 1`` levels (a replay of up to
65 536 rows: the top block is the whole tree), else levels ``1.._TOP_LEVELS``
on top and the rest by rows (at 1e6 rows: a 512 KiB top block, 4 rows per
target), with the top stage going deeper only where more than
``_ROW_LEVELS`` levels would be left.  Both stages read the stored float32
nodes and compare and subtract in the same order, so the result is the
reference's bit for bit.  Leaf index AND leaf priority come back from the
same pass, so the importance-weight computation needs no second gather
round-trip.

``tree_set`` is the write side: scatter a batch of leaf priorities and
recompute the ancestor partial sums bottom-up, aliasing the tree in/out so
the update is in-place.  Scatter does not lower on Mosaic, so ``tree_set``
stays the interpret-mode/CPU reference; ``tree_set_onehot`` is the
TPU-lowerable twin that expresses the same update scatter-free: write a
batch of leaf *deltas* (new - old, duplicate indices masked keep-last) and
add each delta to its ancestor at every level, as a one-hot matmul
``(row one-hot * delta)^T @ lane one-hot`` over row blocks on wide levels.
``ops.sumtree_set`` routes ``backend="pallas"`` to the scatter kernel under
interpret mode and to the one-hot kernel when real-lowering, so sampling
AND priority refresh are both fused on hardware.

All kernels are validated in interpret mode against ``ref.py`` in
tests/test_kernels.py; tests/test_tpu_compile.py compiles the
TPU-lowered ones for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128            # the tree is laid out (rows, 128) in VMEM
_MXU_ROWS = 128         # levels this many rows wide use the one-hot matmul
_CHUNK = 1 << 17        # nodes one one-hot matmul spans (512 KiB of f32)
_SET_ROWS = 256         # leaves one set kernel writes (its masks are n x n)
_SCOPED_VMEM = 16 << 20     # Mosaic's default scoped-VMEM limit (v5e)
_KERNEL_TEMPS = 8 << 20     # a kernel's own temporaries, with room
_TOP_LEVELS = 16        # a deep tree's sample descends levels 1..16 in VMEM
_ROW_LEVELS = 7         # and at most 7 more by DMA'd rows (2**7 = 128 lanes)


def _vmem_params(nbytes: int):
    """Compiler parameters for a kernel that holds ``nbytes`` of the tree in
    VMEM (its input block, and a set's output block too). Where XLA has not
    placed the tree in VMEM already (a program whose other ops take it),
    the kernel stages those blocks itself: a whole 1e6-row tree (8 MiB)
    then needs more than the default limit. ``None`` (the default) where
    two such blocks and the temporaries fit it."""
    need = 2 * nbytes + _KERNEL_TEMPS
    if need <= _SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need + _KERNEL_TEMPS)


def _as_rows(tree: jax.Array) -> jax.Array:
    """Flat ``(2**depth,)`` tree -> lane-dense ``(rows, 128)`` (zero-padded
    below 128 nodes; padding nodes are never addressed)."""
    size = tree.shape[0]
    if size < _LANES:
        tree = jnp.pad(tree, (0, _LANES - size))
    return tree.reshape(-1, _LANES)


def _level_rows(level: int) -> tuple[int, int]:
    """Row range ``[lo, hi)`` holding the nodes of tree level ``level``
    (absolute ids ``[2**level, 2**(level+1))``)."""
    s = 1 << level
    if s < _LANES:
        return 0, 1
    return s // _LANES, 2 * s // _LANES


def _lookup(tree_ref, node, level: int, block: int):
    """``tree[node]`` for an ``(n, 1)`` column of node ids on one level.

    Gather-free, so it lowers on Mosaic: wide levels select each node's
    row with a one-hot matmul at fp32 precision (exact: one-hot weights
    are 0/1), narrow ones with a VPU select per row; the lane is then
    picked with a masked lane reduction.
    """
    n = node.shape[0]
    row, lane = node // _LANES, node % _LANES
    lo, hi = _level_rows(level)
    if hi - lo >= _MXU_ROWS:
        vals = jnp.zeros((n, _LANES), jnp.float32)
        for r0 in range(lo, hi, block):
            r1 = min(r0 + block, hi)
            oh = (row == r0 + jax.lax.broadcasted_iota(
                jnp.int32, (n, r1 - r0), 1)).astype(jnp.float32)
            vals += jnp.dot(oh, tree_ref[r0:r1, :],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    else:
        vals = jnp.broadcast_to(tree_ref[lo:lo + 1, :], (n, _LANES))
        for r in range(lo + 1, hi):
            vals = jnp.where(row == r, tree_ref[r:r + 1, :], vals)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)
    return jnp.sum(jnp.where(lanes == lane, vals, 0.0), axis=1,
                   keepdims=True)


def _descend(lookup, t, node, levels: range):
    """Descend ``levels`` from ``node`` for an ``(n, 1)`` column of target
    masses; ``lookup(left, level)`` gives the left children's masses.
    Returns the last level's node ids and the residual targets."""
    for lvl in levels:                  # static unroll
        left = 2 * node
        lmass = lookup(left, lvl)
        go_right = t >= lmass
        t = jnp.where(go_right, t - lmass, t)
        node = jnp.where(go_right, left + 1, left)
    return node, t


def _descend_vmem(tree_ref, t_ref, levels: int, block: int):
    """Levels ``1..levels`` from the root, on rows of the tree in VMEM."""
    t = t_ref[...].astype(jnp.float32)
    return _descend(lambda left, lvl: _lookup(tree_ref, left, lvl, block),
                    t, jnp.ones(t.shape, jnp.int32), range(1, levels + 1))


def _sample_kernel(tree_ref, t_ref, leaf_ref, pri_ref, *, depth: int,
                   capacity: int, block: int):
    """The whole descent on a tree held in VMEM (a shallow tree)."""
    half = 1 << (depth - 1)
    node, _ = _descend_vmem(tree_ref, t_ref, depth - 1, block)
    # clamp into the valid leaf range (zero-priority padding tail)
    leaf = jnp.clip(node - half, 0, capacity - 1)
    leaf_ref[...] = leaf
    pri_ref[...] = _lookup(tree_ref, leaf + half, depth - 1, block)


def _top_kernel(tree_ref, t_ref, node_ref, res_ref, *, split: int,
                block: int):
    """Top stage: levels ``1..split`` on the tree's first rows in VMEM."""
    node, t = _descend_vmem(tree_ref, t_ref, split, block)
    node_ref[...] = node
    res_ref[...] = t


def _row_kernel(ids_ref, node_ref, t_ref, tree_hbm, leaf_ref, pri_ref,
                rows, last, sem, *, depth: int, split: int, capacity: int):
    """Row stage: levels ``split+1..depth-1`` from rows fetched by DMA.

    A level-``split`` node ``a`` has its level-``split+k`` descendants at
    ``[a << k, (a + 1) << k)``, inside one 128-lane row for ``k <= 7``. So
    each target's rows are fixed by ``a`` (read from SMEM) and every copy
    is issued before the first wait. ``last`` is the row of leaf
    ``capacity - 1``, whose priority a clamped target returns.
    """
    bt = t_ref.shape[0]
    levels = depth - 1 - split
    half = 1 << (depth - 1)
    last_node = capacity - 1 + half

    def copy(j, k, a):          # a's level-(split+k) row -> rows[k-1, j]
        return pltpu.make_async_copy(
            tree_hbm.at[pl.ds((a << k) // _LANES, 1)],
            rows.at[k - 1, pl.ds(j, 1)], sem)

    last_copy = pltpu.make_async_copy(
        tree_hbm.at[pl.ds(last_node // _LANES, 1)], last, sem)
    last_copy.start()

    def start(j, carry):
        a = ids_ref[j, 0]
        for k in range(1, levels + 1):
            copy(j, k, a).start()
        return carry

    def wait(j, carry):
        for k in range(1, levels + 1):
            copy(j, k, 0).wait()
        return carry

    jax.lax.fori_loop(0, bt, start, 0)
    jax.lax.fori_loop(0, bt, wait, 0)
    last_copy.wait()

    lanes = jax.lax.broadcasted_iota(jnp.int32, (bt, _LANES), 1)
    pick = lambda vals, node: jnp.sum(
        jnp.where(lanes == node % _LANES, vals, 0.0), axis=1, keepdims=True)
    node, _ = _descend(lambda left, lvl: pick(rows[lvl - split - 1], left),
                       t_ref[...], node_ref[...], range(split + 1, depth))
    clamped = node > last_node
    leaf_ref[...] = jnp.clip(node - half, 0, capacity - 1)
    pri_ref[...] = jnp.where(
        clamped, last[:, last_node % _LANES:last_node % _LANES + 1],
        pick(rows[levels - 1], node))


def _split(depth: int) -> int:
    """The deepest level the top stage descends, from the tree's depth.

    The top stage holds levels ``0..s`` (``2**(s + 1)`` nodes) in VMEM;
    the row stage takes levels ``s+1..depth-1`` by DMA, at most
    ``_ROW_LEVELS`` of them (the descendants one row holds). A tree no
    deeper than ``_TOP_LEVELS + 1`` takes the top stage alone.
    """
    return max(min(depth - 1, _TOP_LEVELS), depth - 1 - _ROW_LEVELS)


@functools.partial(jax.jit, static_argnames=("capacity", "bt", "interpret"))
def tree_sample(tree: jax.Array, targets: jax.Array, *, capacity: int,
                bt: int = 128, interpret: bool = True
                ) -> tuple[jax.Array, jax.Array]:
    """Proportional descent for a batch of target masses.

    tree: (2**depth,) float32; targets: (B,) with B a multiple of ``bt``
    (ops.py pads).  Returns (leaf_idx int32, leaf_priority f32), both (B,).
    """
    depth = tree.shape[0].bit_length() - 1
    split = _split(depth)
    (b,) = targets.shape
    assert b % bt == 0, (b, bt)
    rows = _as_rows(tree)
    col = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((b, 1), jnp.int32),
                 jax.ShapeDtypeStruct((b, 1), jnp.float32)]
    block = _CHUNK // _LANES
    if split == depth - 1:
        kernel = functools.partial(_sample_kernel, depth=depth,
                                   capacity=capacity, block=block)
        top = rows.shape
    else:
        kernel = functools.partial(_top_kernel, split=split, block=block)
        top = ((2 << split) // _LANES, _LANES)     # levels 0..split
    out = pl.pallas_call(
        kernel,
        grid=(b // bt,),
        in_specs=[pl.BlockSpec(top, lambda i: (0, 0)), col],
        out_specs=[col, col],
        out_shape=out_shape,
        compiler_params=_vmem_params(top[0] * _LANES * rows.dtype.itemsize),
        interpret=interpret,
    )(rows, targets.reshape(b, 1))
    if split < depth - 1:
        node, res = out
        out = pl.pallas_call(
            functools.partial(_row_kernel, depth=depth, split=split,
                              capacity=capacity),
            grid=(b // bt,),
            in_specs=[pl.BlockSpec((bt, 1), lambda i: (i, 0),
                                   memory_space=pltpu.SMEM),
                      col, col, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[col, col],
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((depth - 1 - split, bt, _LANES), jnp.float32),
                pltpu.VMEM((1, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
            interpret=interpret,
        )(node, node, res, rows)
    leaf, pri = out
    return leaf[:, 0], pri[:, 0]


def _set_kernel(tree_ref, idx_ref, val_ref, out_ref, *, depth: int):
    tree = tree_ref[0, :]
    half = tree.shape[0] // 2
    leaf = idx_ref[0, :] + half
    tree = tree.at[leaf].set(val_ref[0, :].astype(tree.dtype))
    node = leaf // 2
    for _ in range(depth - 1):          # recompute levels depth-2 .. 0
        tree = tree.at[node].set(jnp.take(tree, 2 * node)
                                 + jnp.take(tree, 2 * node + 1))
        node = node // 2
    out_ref[0, :] = tree


@functools.partial(jax.jit, static_argnames=("interpret",))
def tree_set(tree: jax.Array, idx: jax.Array, value: jax.Array, *,
             interpret: bool = True) -> jax.Array:
    """Batch leaf write + ancestor resum; returns the updated tree.

    The tree input is donated to the output (in-place update); duplicate
    ``idx`` resolve to an unspecified writer, same caveat as the XLA ref.
    """
    size = tree.shape[0]
    depth = size.bit_length() - 1
    (n,) = idx.shape
    return pl.pallas_call(
        functools.partial(_set_kernel, depth=depth),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, size), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, size), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, size), tree.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(tree.reshape(1, size), idx.reshape(1, n).astype(jnp.int32),
      value.reshape(1, n))[0]


def _set_onehot_kernel(tree_ref, idx_ref, idx_row_ref, val_ref, out_ref,
                       *, depth: int, block: int):
    half = 1 << (depth - 1)
    out_ref[...] = tree_ref[...]
    idx = idx_ref[...]                                  # (n, 1)
    n = idx.shape[0]
    leaf = idx + half
    old = _lookup(tree_ref, leaf, depth - 1, block)
    # keep-LAST duplicate semantics (the host SumTree's): mask every write
    # that has a later duplicate, then deltas of distinct leaves sum freely
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    later_dup = (idx == idx_row_ref[...]) & (jj > ii)
    keep = jnp.logical_not(jnp.any(later_dup, axis=1, keepdims=True))
    delta = (val_ref[...].astype(jnp.float32) - old) * keep.astype(
        jnp.float32)                                    # (n, 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)
    for lvl in range(depth - 1, -1, -1):                # leaves -> root
        anc = leaf >> (depth - 1 - lvl)                 # ancestor node ids
        row = anc // _LANES
        at_lane = lanes == anc % _LANES
        lo, hi = _level_rows(lvl)
        if hi - lo >= _MXU_ROWS:
            # (rows, 128) increments = (row one-hot * delta)^T @ lane one-hot
            for r0 in range(lo, hi, block):
                r1 = min(r0 + block, hi)
                a = jnp.where(row == r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (n, r1 - r0), 1), delta, 0.0)
                out_ref[r0:r1, :] += jax.lax.dot_general(
                    a, at_lane.astype(jnp.float32), (((0,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        else:
            for r in range(lo, hi):
                out_ref[r:r + 1, :] += jnp.sum(
                    jnp.where(at_lane & (row == r), delta, 0.0), axis=0,
                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def tree_set_onehot(tree: jax.Array, idx: jax.Array, value: jax.Array, *,
                    interpret: bool = True, chunk: int = _CHUNK) -> jax.Array:
    """Scatter-free ``tree_set``: per-level one-hot delta propagation.

    Mathematically identical to ``tree_set``/``ref.tree_set_ref`` with
    keep-last duplicate resolution; lowers on Mosaic because the only data
    movement is dense matmuls, masked reductions and static slices of the
    ``(rows, 128)`` tree. ``chunk`` bounds the nodes one one-hot matmul
    spans on wide levels (must be a power of two).

    More than ``_SET_ROWS`` leaves (a replay warm-up) are set in pieces of
    that many, in order, by a scan: the kernel's ``(n, n)`` duplicate masks
    do not compile at ~10 000 leaves. Later pieces overwrite earlier ones,
    so keep-last holds across pieces; ancestor sums may differ from one
    set's by rounding.
    """
    assert chunk & (chunk - 1) == 0, chunk
    (n,) = idx.shape
    idx = idx.astype(jnp.int32)
    if n <= _SET_ROWS:
        return _set_onehot(tree, idx, value, interpret=interpret, chunk=chunk)
    k = n // _SET_ROWS
    head = k * _SET_ROWS
    tree, _ = jax.lax.scan(
        lambda t, piece: (_set_onehot(t, *piece, interpret=interpret,
                                      chunk=chunk), None),
        tree, (idx[:head].reshape(k, _SET_ROWS),
               value[:head].reshape(k, _SET_ROWS)))
    if n > head:
        tree = _set_onehot(tree, idx[head:], value[head:],
                           interpret=interpret, chunk=chunk)
    return tree


def _set_onehot(tree: jax.Array, idx: jax.Array, value: jax.Array, *,
                interpret: bool, chunk: int) -> jax.Array:
    """One kernel call of ``tree_set_onehot`` (int32 ``idx``)."""
    size = tree.shape[0]
    depth = size.bit_length() - 1
    (n,) = idx.shape
    rows = _as_rows(tree)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_set_onehot_kernel, depth=depth,
                          block=max(chunk // _LANES, 1)),
        grid=(1,),
        in_specs=[full(rows.shape), full((n, 1)), full((1, n)),
                  full((n, 1))],
        out_specs=full(rows.shape),
        out_shape=jax.ShapeDtypeStruct(rows.shape, tree.dtype),
        input_output_aliases={0: 0},
        compiler_params=_vmem_params(rows.nbytes),
        interpret=interpret,
    )(rows, idx.reshape(n, 1), idx.reshape(1, n), value.reshape(n, 1))
    return out.reshape(-1)[:size]
