"""Flash attention Pallas kernel (causal / sliding-window / softcap).

Grid (batch*heads, num_q_blocks, num_kv_blocks); the kv axis is innermost so
the online-softmax accumulators (m, l, acc) live in VMEM scratch across kv
steps. Per-block work is one (bq, d) x (d, bkv) MXU matmul + one
(bq, bkv) x (bkv, d) matmul; masks are built from program ids — the mask
tensor never exists in HBM. float32 statistics regardless of input dtype.

The prefill path of every attention arch lowers to this kernel on TPU;
interpret=True validates it on CPU against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scratch(bq, d):
    return [pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32)]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nkv: int, bq: int, bkv: int, scale: float, causal: bool,
            window: int, softcap: float):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale             # (bq, d)
    k = k_ref[0].astype(jnp.float32)                     # (bkv, d)
    s = q @ k.T                                          # (bq, bkv)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    kv_pos = kv_idx * bkv + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 1)
    ok = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        ok &= kv_pos <= q_pos
    if window:
        ok &= q_pos - kv_pos < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + \
        p @ v_ref[0].astype(jnp.float32)

    @pl.when(kv_idx == nkv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bkv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 128, bkv: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (BH, Sq, d); k, v: (BH, Skv, d). GQA callers fold/broadcast heads.

    Returns (BH, Sq, d). Sq % bq == 0 and Skv % bkv == 0 required.
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % bq == 0 and skv % bkv == 0, (q.shape, k.shape, bq, bkv)
    nq, nkv = sq // bq, skv // bkv
    scale = d ** -0.5
    return pl.pallas_call(
        functools.partial(_kernel, nkv=nkv, bq=bq, bkv=bkv, scale=scale,
                          causal=causal, window=window, softcap=softcap),
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=_scratch(bq, d),
        interpret=interpret,
    )(q, k, v)
