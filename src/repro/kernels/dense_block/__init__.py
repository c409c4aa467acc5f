"""Fused kernels for the paper's wide MLP-DenseNet hot path.

Two granularities:

* ``dense_block.py`` / ``ops.py`` — single fused dense layer
  (``act(x @ w + b)``, MXU-tiled) and ``dense_concat_matmul``, which splits
  W row-wise per stream segment so one DenseNet layer's concat never
  materializes. ``interpret=None`` auto-selects real Mosaic lowering on TPU
  and the Pallas interpreter elsewhere.
* ``stack.py`` — the whole L-layer stack in one pass, forward AND backward
  (``jax.custom_vjp``). This is what ``core.blocks.mlp_block_apply``
  routes to under ``backend="fused"`` and what SAC/TD3/OFENet train
  through via ``ExperimentSpec`` ``network.block_backend="fused"``.

Stream-in-VMEM layout (stack.py): a per-batch-tile VMEM scratch holds the
growing concat stream —

    densenet  [ x | y_0 | ... | y_{L-1} ]   each layer matmuls the prefix
    d2rl      [ x | h ]                     h slot rewritten per layer
    mlp       [ h ]                         single slot, rewritten

Weights are pre-scattered row-segment-wise into the same (lane-padded)
layout, so each layer is one ``prefix @ W`` contraction; bias + activation
fuse in, and only the final feature leaves VMEM. The backward kernel
recomputes the stream from the checkpointed input in scratch, then streams
``dL/dW`` row-segment blocks out, accumulated across batch tiles: O(L)
HBM traffic in both directions vs the jnp loop's O(L^2).

Supported / fallback matrix (``mlp_block_apply``, see MLPBlockConfig):

    fused   densenet | d2rl | mlp, swish | silu | relu | tanh | identity,
            batch_norm=False, num_layers >= 1   (the paper's SAC setting)
    jnp     everything else: resnet (skip-add), batch_norm=True (running
            stats + cross-replica psum), gelu, num_layers == 0

The fallback is silent and exact: unsupported configs keep the reference
loop. On TPU the fused kernel keeps weights in VMEM, so past the widths
listed in ``stack.py`` (e.g. a densenet SAC trunk wider than U=512) the
training program fails to compile instead (ROADMAP R2).
"""
