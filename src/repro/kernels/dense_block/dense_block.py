"""Fused dense-layer kernel: out = act(x @ w + b), MXU-tiled.

The paper's hot spot is the wide DenseNet layer ``swish(concat(stream) @ W)``
with a concat-growing K dimension (2159 -> 4207 -> 6255 on Ant, Table 2).
This kernel is the TPU-native building block (DESIGN.md §2): (bm, bn, bk)
VMEM tiles aligned to the 128x128 MXU, float32 accumulation in a VMEM
scratch across the K grid axis, bias + activation fused into the final
K step (no extra HBM round-trip for the pre-activation).

The DenseNet concat itself never materializes: ``ops.dense_concat_matmul``
splits W row-wise per stream segment and accumulates partial products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

_ACTS = {
    "identity": lambda x: x,
    "swish": lambda x: x * jax.nn.sigmoid(x),
    "relu": lambda x: jnp.maximum(x, 0.0),
}


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk: int, activation: str,
            add_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        acc = acc_ref[...]
        if add_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[...] = _ACTS[activation](acc).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "bm", "bn", "bk",
                                             "interpret"))
def fused_dense(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                activation: str = "swish", bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool | None = None) -> jax.Array:
    """act(x @ w + b). x: (M, K); w: (K, N); b: (N,) or None.

    M, K, N must be multiples of the block sizes (callers pad; the paper's
    widths are powers of two after the first layer, and we round the stream
    segments up in ops.py). ``interpret=None`` auto-selects: real Mosaic
    lowering on TPU, the Pallas interpreter elsewhere.
    """
    interpret = default_interpret(interpret)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        x.shape, w.shape, (bm, bn, bk))
    nk = k // bk
    add_bias = b is not None
    if b is None:
        b = jnp.zeros((n,), x.dtype)
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, activation=activation,
                          add_bias=add_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, b.reshape(1, n))
