"""Fused streaming MLP-DenseNet *stack*: the whole L-layer block in one pass.

``core.blocks.mlp_block_apply`` re-materializes the growing concat stream at
every DenseNet layer — O(L^2) memory traffic per forward, and reverse-mode
autodiff of that loop checkpoints every per-layer concat (O(L^2) residual
bytes) on top. This module runs the entire stack with O(L) traffic in both
directions and is the first kernel the RL agents *train through*
(``replay_tree`` is data-path only).

Forward (``impl="pallas"``): one ``pallas_call`` over batch tiles. The
concat stream lives in a VMEM scratch accumulator laid out as

    densenet  [ x | y_0 | y_1 | ... | y_{L-1} ]      (prefix grows by up)
    d2rl      [ x | h ]                              (h slot rewritten)
    mlp       [ h ]                                  (slot rewritten)

and each layer is ONE matmul of the current stream prefix against its
weight, whose rows the host-side wrapper pre-scatters into the same padded
layout (the row-segment generalization of ``ops.dense_concat_matmul`` — the
concat itself never exists, in VMEM or HBM). Bias + activation fuse into
the same step; only the final feature block leaves VMEM.

Backward (``jax.custom_vjp``): the Pallas kernel checkpoints nothing but
the layer *input* — it recomputes the stream (and pre-activations) in VMEM
scratch from ``x``, then runs the reverse sweep in the same kernel,
accumulating each ``dL/dW`` row-segment block across batch tiles so weight
gradients stream out exactly once. HBM traffic is O(L) segments in, O(L)
segments out.

``impl="xla"`` is the same streaming algorithm written as jittable XLA — the
interpret-off oracle used on CPU (where interpret-mode Pallas only checks
correctness) and the default off-TPU. Its custom VJP keeps the gradient
stream **transposed** so both the ``dW`` (stream^T @ gz) and ``dx``
(W @ gz^T) matmuls hit XLA:CPU's fast canonical layouts — on CPU this is
where the measured fwd+bwd win over the autodiffed jnp loop comes from
(~1.8x at L=8/U=1024, ~1.3-1.5x at U=512; benchmarks/dense_stack.py). For
densenet the forward output *is* the stream buffer, so it rides along as a
free residual; ``remat=True`` instead recomputes everything from the
checkpointed input, matching the Pallas kernel's memory profile.

Supported: connectivity in {densenet, d2rl, mlp}, activation in
{swish, silu, relu, tanh, identity}, no batch norm — the paper's SAC
setting. ``core.blocks.mlp_block_apply(backend="fused")`` routes here and
falls back to the jnp loop for everything else (BN, resnet, gelu).

VMEM note: weights + dW accumulators stay resident across batch tiles, and
XLA may place small kernel operands in the kernel's 16 MiB of scoped VMEM.
Forward + backward compile for TPU v5e (batch 256, input width 256;
tests/test_tpu_compile.py) up to
    densenet  L=2 U=1024 | L=4 U=512  | L=8 U=128
    d2rl      L=2 U=2048 | L=8 U=1024
    mlp       L=2 U=1024 | L=4 U=512  | L=8 U=256
and inside the SAC training chunk (inputs up to 516 wide) the L=2 densenet
trunk only up to U=512. Wider needs the K-tiled layer streaming of ROADMAP
R2; the XLA path has no such limit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret, mosaic_available

FUSED_CONNECTIVITIES = ("mlp", "densenet", "d2rl")
FUSED_ACTIVATIONS = ("swish", "silu", "relu", "tanh", "identity")
_LANE = 128                      # TPU lane width; padded column granularity


def _act_pair(name: str):
    """(activation, d-activation/d-preactivation) as closed forms."""
    if name in ("swish", "silu"):
        def act(z):
            return z * jax.nn.sigmoid(z)

        def dact(z):
            s = jax.nn.sigmoid(z)
            return s * (1.0 + z * (1.0 - s))
    elif name == "relu":
        def act(z):
            return jnp.maximum(z, 0.0)

        def dact(z):
            return (z > 0).astype(z.dtype)
    elif name == "tanh":
        act = jnp.tanh

        def dact(z):
            return 1.0 - jnp.tanh(z) ** 2
    elif name == "identity":
        def act(z):
            return z

        def dact(z):
            return jnp.ones_like(z)
    else:
        raise ValueError(
            f"activation {name!r} not fused; have {FUSED_ACTIVATIONS}")
    return act, dact


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass(frozen=True)
class _StackPlan:
    """Static geometry of one fused stack call (hashable: jit/vjp key).

    All ``*p`` quantities are lane-padded; the ``w_rowmap`` entries say
    where each logical weight row-segment lands in the padded layout the
    kernels consume (dst_row, src_row, n_rows).
    """
    connectivity: str
    activation: str
    num_layers: int
    d0: int                      # logical input width
    u: int                       # logical layer width
    impl: str                    # "xla" | "pallas"
    interpret: bool
    remat: bool
    block_m: int

    @property
    def d0p(self) -> int:
        return _ceil_to(self.d0, _LANE)

    @property
    def up(self) -> int:
        return _ceil_to(self.u, _LANE)

    @property
    def acc_w(self) -> int:
        """VMEM stream accumulator width."""
        if self.connectivity == "densenet":
            return self.d0p + self.num_layers * self.up
        if self.connectivity == "d2rl":
            return self.d0p + self.up
        return max(self.d0p, self.up)

    @property
    def feat_w(self) -> int:
        """Padded width of the kernel's feature output."""
        return self.acc_w if self.connectivity == "densenet" else self.up

    @property
    def feat_dim(self) -> int:
        """Logical feature width (matches MLPBlockConfig.feature_dim)."""
        if self.connectivity == "densenet":
            return self.d0 + self.num_layers * self.u
        return self.u

    def in_dim(self, i: int) -> int:
        """Logical input width of layer i (matches layer_in_dims)."""
        if self.connectivity == "densenet":
            return self.d0 + i * self.u
        if i == 0:
            return self.d0
        return self.u + self.d0 if self.connectivity == "d2rl" else self.u

    def in_w(self, i: int) -> int:
        """Padded stream-prefix width layer i's matmul consumes."""
        if self.connectivity == "densenet":
            return self.d0p + i * self.up
        if i == 0:
            return self.d0p
        return self.d0p + self.up if self.connectivity == "d2rl" else self.up

    def out_off(self, i: int) -> int:
        """Padded column where layer i's activation is written."""
        if self.connectivity == "densenet":
            return self.d0p + i * self.up
        return self.d0p if self.connectivity == "d2rl" else 0

    def w_rowmap(self, i: int) -> Tuple[Tuple[int, int, int], ...]:
        """(dst_padded_row, src_logical_row, n_rows) per stream segment."""
        if self.connectivity == "densenet":
            return ((0, 0, self.d0),) + tuple(
                (self.d0p + j * self.up, self.d0 + j * self.u, self.u)
                for j in range(i))
        if self.connectivity == "d2rl" and i > 0:
            # logical rows are [h | x]; acc layout is [x | h]
            return ((0, self.u, self.d0), (self.d0p, 0, self.u))
        return ((0, 0, self.in_dim(i)),)

    def feat_segs(self) -> Tuple[Tuple[int, int, int], ...]:
        """(logical_col, padded_col, n_cols) segments of the feature."""
        if self.connectivity == "densenet":
            return ((0, 0, self.d0),) + tuple(
                (self.d0 + i * self.u, self.d0p + i * self.up, self.u)
                for i in range(self.num_layers))
        return ((0, 0, self.u),)

    @property
    def pad_trivial(self) -> bool:
        return self.d0p == self.d0 and self.up == self.u

    def rows_identity(self, i: int) -> bool:
        """True iff layer i's padded row layout equals the logical order.

        False for d2rl layers past the first even when ``pad_trivial``:
        logical rows are [h | x] but the accumulator streams [x | h].
        """
        return all(dst == src for dst, src, _n in self.w_rowmap(i))


# ---------------------------------------------------------------------------
# jnp-loop reference oracle (mirrors core.blocks.mlp_block_apply, no BN)
# ---------------------------------------------------------------------------

def dense_stack_ref(x: jax.Array, ws: Sequence[jax.Array],
                    bs: Sequence[jax.Array], *,
                    connectivity: str = "densenet",
                    activation: str = "swish") -> jax.Array:
    """The O(L^2)-traffic concat loop — ground truth for the fused paths."""
    act = _act_pair(activation)[0]
    stream, h = x, x
    for i, (w, b) in enumerate(zip(ws, bs)):
        if connectivity == "densenet":
            inp = stream
        elif connectivity == "d2rl" and i > 0:
            inp = jnp.concatenate([h, x], axis=-1)
        else:
            inp = h
        h = act(inp @ w + b)
        if connectivity == "densenet":
            stream = jnp.concatenate([stream, h], axis=-1)
    return stream if connectivity == "densenet" else h


# ---------------------------------------------------------------------------
# XLA streaming implementation (the CPU/off-TPU oracle, interpret-free)
# ---------------------------------------------------------------------------

def _xla_forward(plan: _StackPlan, x, ws, bs):
    """Streaming forward; returns (feature, per-layer pre-activations)."""
    act = _act_pair(plan.activation)[0]
    L, d0, u = plan.num_layers, plan.d0, plan.u
    zs: List[jax.Array] = []
    if plan.connectivity == "densenet":
        buf = jnp.zeros(x.shape[:-1] + (d0 + L * u,), x.dtype)
        buf = buf.at[..., :d0].set(x)
        for i in range(L):
            d = d0 + i * u
            z = buf[..., :d] @ ws[i] + bs[i]
            zs.append(z)
            buf = buf.at[..., d:d + u].set(act(z))
        return buf, zs
    h = x
    for i in range(L):
        if plan.connectivity == "d2rl" and i > 0:
            inp = jnp.concatenate([h, x], axis=-1)
        else:
            inp = h
        z = inp @ ws[i] + bs[i]
        zs.append(z)
        h = act(z)
    return h, zs


def _xla_backward(plan: _StackPlan, x, ws, zs, g, buf=None):
    """Reverse sweep with a *transposed* gradient stream.

    ``dW_i = stream_i^T @ gz_i`` and ``dstream += W_i @ gz_i^T`` are both
    canonical (contract-inner-dims) matmuls in this layout; the naive
    ``gz @ W^T`` pattern runs at roughly half throughput on XLA:CPU.
    """
    act, dact = _act_pair(plan.activation)
    L, d0, u = plan.num_layers, plan.d0, plan.u
    dws: List[jax.Array] = [x] * L      # placeholders, overwritten below
    dbs: List[jax.Array] = [x] * L
    if plan.connectivity == "densenet":
        # for densenet the forward output IS the stream buffer, so the fwd
        # rule saves it as a (free) residual; remat mode rebuilds it here
        if buf is None:
            buf = jnp.concatenate([x] + [act(z) for z in zs], axis=-1)
        gbt = g.T
        for i in reversed(range(L)):
            d = d0 + i * u
            gzt = gbt[d:d + u, :] * dact(zs[i]).T
            dws[i] = jax.lax.dot_general(buf[:, :d], gzt,
                                         (((0,), (1,)), ((), ())))
            dbs[i] = jnp.sum(gzt, axis=1)
            gbt = gbt.at[:d, :].add(ws[i] @ gzt)
        return gbt[:d0, :].T, dws, dbs
    ght = g.T
    gxt = jnp.zeros((d0, x.shape[0]), x.dtype)
    for i in reversed(range(L)):
        gzt = ght * dact(zs[i]).T
        h_prev = x if i == 0 else act(zs[i - 1])
        if plan.connectivity == "d2rl" and i > 0:
            inp = jnp.concatenate([h_prev, x], axis=-1)
        else:
            inp = h_prev
        dws[i] = jax.lax.dot_general(inp, gzt, (((0,), (1,)), ((), ())))
        dbs[i] = jnp.sum(gzt, axis=1)
        if i == 0:
            gxt = gxt + ws[0] @ gzt
        elif plan.connectivity == "d2rl":
            ght = ws[i][:u] @ gzt
            gxt = gxt + ws[i][u:] @ gzt
        else:
            ght = ws[i] @ gzt
    return gxt.T, dws, dbs


# ---------------------------------------------------------------------------
# Pallas kernels: stream-in-VMEM forward + recompute backward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, *refs, plan: _StackPlan):
    L = plan.num_layers
    w_refs, b_refs = refs[:L], refs[L:2 * L]
    o_ref, acc_ref = refs[2 * L], refs[2 * L + 1]
    act = _act_pair(plan.activation)[0]
    up = plan.up
    acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[:, :plan.d0p] = x_ref[...].astype(jnp.float32)
    for i in range(L):
        z = jnp.dot(acc_ref[:, :plan.in_w(i)], w_refs[i][...],
                    preferred_element_type=jnp.float32) + b_refs[i][...]
        acc_ref[:, plan.out_off(i):plan.out_off(i) + up] = act(z)
    if plan.connectivity == "densenet":
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)
    else:
        off = plan.out_off(L - 1)
        o_ref[...] = acc_ref[:, off:off + up].astype(o_ref.dtype)


def _bwd_kernel(x_ref, g_ref, *refs, plan: _StackPlan):
    L = plan.num_layers
    w_refs, b_refs = refs[:L], refs[L:2 * L]
    dx_ref = refs[2 * L]
    dw_refs = refs[2 * L + 1:3 * L + 1]
    db_refs = refs[3 * L + 1:4 * L + 1]
    acc_ref, zs_ref, gb_ref = refs[4 * L + 1:4 * L + 4]
    act, dact = _act_pair(plan.activation)
    up, d0p = plan.up, plan.d0p

    @pl.when(pl.program_id(0) == 0)
    def _init():                          # dW/db accumulate across batch tiles
        for li in range(L):
            dw_refs[li][...] = jnp.zeros_like(dw_refs[li])
            db_refs[li][...] = jnp.zeros_like(db_refs[li])

    # recompute the stream + pre-activations from the checkpointed input
    acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[:, :d0p] = x_ref[...].astype(jnp.float32)
    for i in range(L):
        z = jnp.dot(acc_ref[:, :plan.in_w(i)], w_refs[i][...],
                    preferred_element_type=jnp.float32) + b_refs[i][...]
        zs_ref[:, i * up:(i + 1) * up] = z
        acc_ref[:, plan.out_off(i):plan.out_off(i) + up] = act(z)

    nt = (((1,), (1,)), ((), ()))         # gz @ W^T via dot_general
    tn = (((0,), (0,)), ((), ()))         # stream^T @ gz via dot_general
    if plan.connectivity == "densenet":
        gb_ref[...] = g_ref[...].astype(jnp.float32)
        for i in reversed(range(L)):
            k, off = plan.in_w(i), plan.out_off(i)
            gz = gb_ref[:, off:off + up] * dact(zs_ref[:, i * up:(i + 1) * up])
            dw_refs[i][...] += jax.lax.dot_general(
                acc_ref[:, :k], gz, tn, preferred_element_type=jnp.float32)
            db_refs[i][...] += jnp.sum(gz, axis=0, keepdims=True)
            gb_ref[:, :k] += jax.lax.dot_general(
                gz, w_refs[i][...], nt, preferred_element_type=jnp.float32)
        dx_ref[...] = gb_ref[:, :d0p].astype(dx_ref.dtype)
        return
    gh = g_ref[...].astype(jnp.float32)
    gx = jnp.zeros((x_ref.shape[0], d0p), jnp.float32)
    for i in reversed(range(L)):
        gz = gh * dact(zs_ref[:, i * up:(i + 1) * up])
        db_refs[i][...] += jnp.sum(gz, axis=0, keepdims=True)
        if i == 0:
            dw_refs[0][...] += jax.lax.dot_general(
                x_ref[...].astype(jnp.float32), gz, tn,
                preferred_element_type=jnp.float32)
            gx += jax.lax.dot_general(gz, w_refs[0][...], nt,
                                      preferred_element_type=jnp.float32)
        else:
            h_prev = act(zs_ref[:, (i - 1) * up:i * up])
            if plan.connectivity == "d2rl":
                # padded rows: [0:d0p] = x segment, [d0p:] = h segment
                dw_refs[i][:d0p, :] += jax.lax.dot_general(
                    x_ref[...].astype(jnp.float32), gz, tn,
                    preferred_element_type=jnp.float32)
                dw_refs[i][d0p:, :] += jax.lax.dot_general(
                    h_prev, gz, tn, preferred_element_type=jnp.float32)
                gx += jax.lax.dot_general(gz, w_refs[i][:d0p, :], nt,
                                          preferred_element_type=jnp.float32)
                gh = jax.lax.dot_general(gz, w_refs[i][d0p:, :], nt,
                                         preferred_element_type=jnp.float32)
            else:
                dw_refs[i][...] += jax.lax.dot_general(
                    h_prev, gz, tn, preferred_element_type=jnp.float32)
                gh = jax.lax.dot_general(gz, w_refs[i][...], nt,
                                         preferred_element_type=jnp.float32)
    dx_ref[...] = gx.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("plan",))
def _pallas_forward(plan: _StackPlan, x, ws, bs):
    m = x.shape[0]
    bm = plan.block_m
    in_specs = [pl.BlockSpec((bm, plan.d0p), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec((plan.in_w(li), plan.up), lambda i: (0, 0))
                 for li in range(plan.num_layers)]
    in_specs += [pl.BlockSpec((1, plan.up), lambda i: (0, 0))
                 for _ in range(plan.num_layers)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, plan.feat_w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, plan.feat_w), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, plan.acc_w), jnp.float32)],
        interpret=plan.interpret,
    )(x, *ws, *bs)


@functools.partial(jax.jit, static_argnames=("plan",))
def _pallas_backward(plan: _StackPlan, x, g, ws, bs):
    m = x.shape[0]
    bm = plan.block_m
    L = plan.num_layers
    in_specs = [pl.BlockSpec((bm, plan.d0p), lambda i: (i, 0)),
                pl.BlockSpec((bm, plan.feat_w), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec((plan.in_w(li), plan.up), lambda i: (0, 0))
                 for li in range(L)]
    in_specs += [pl.BlockSpec((1, plan.up), lambda i: (0, 0))
                 for _ in range(L)]
    out_specs = [pl.BlockSpec((bm, plan.d0p), lambda i: (i, 0))]
    out_specs += [pl.BlockSpec((plan.in_w(li), plan.up), lambda i: (0, 0))
                  for li in range(L)]
    out_specs += [pl.BlockSpec((1, plan.up), lambda i: (0, 0))
                  for _ in range(L)]
    out_shape = [jax.ShapeDtypeStruct((m, plan.d0p), x.dtype)]
    out_shape += [jax.ShapeDtypeStruct((plan.in_w(li), plan.up), jnp.float32)
                  for li in range(L)]
    out_shape += [jax.ShapeDtypeStruct((1, plan.up), jnp.float32)
                  for _ in range(L)]
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, plan.acc_w), jnp.float32),
                        pltpu.VMEM((bm, L * plan.up), jnp.float32),
                        pltpu.VMEM((bm, plan.acc_w), jnp.float32)],
        interpret=plan.interpret,
    )(x, g, *ws, *bs)
    return outs[0], outs[1:L + 1], outs[L + 1:]


# ------------------------------------------------- padded-layout marshalling

def _pad_x(plan: _StackPlan, x):
    mp = _ceil_to(max(x.shape[0], 1), plan.block_m)
    out = jnp.zeros((mp, plan.d0p), x.dtype)
    return out.at[:x.shape[0], :plan.d0].set(x)


def _pad_w(plan: _StackPlan, i: int, w):
    if (plan.pad_trivial and plan.rows_identity(i)
            and w.shape == (plan.in_w(i), plan.up)):
        return w
    out = jnp.zeros((plan.in_w(i), plan.up), w.dtype)
    for dst, src, n in plan.w_rowmap(i):
        out = out.at[dst:dst + n, :plan.u].set(w[src:src + n])
    return out


def _unpad_dw(plan: _StackPlan, i: int, dwp):
    if (plan.pad_trivial and plan.rows_identity(i)
            and dwp.shape == (plan.in_dim(i), plan.u)):
        return dwp
    segs = sorted(plan.w_rowmap(i), key=lambda s: s[1])   # logical row order
    return jnp.concatenate(
        [dwp[dst:dst + n, :plan.u] for dst, _src, n in segs], axis=0)


def _pad_b(plan: _StackPlan, b):
    return jnp.zeros((1, plan.up), b.dtype).at[0, :plan.u].set(b)


def _pad_feat(plan: _StackPlan, g):
    """Scatter a logical feature(-cotangent) into the padded layout."""
    mp = _ceil_to(max(g.shape[0], 1), plan.block_m)
    if plan.pad_trivial and mp == g.shape[0]:
        return g
    out = jnp.zeros((mp, plan.feat_w), g.dtype)
    for lg, pd, n in plan.feat_segs():
        out = out.at[:g.shape[0], pd:pd + n].set(g[:, lg:lg + n])
    return out


def _unpad_feat(plan: _StackPlan, o, m: int):
    if plan.pad_trivial and o.shape[0] == m:
        return o
    return jnp.concatenate(
        [o[:m, pd:pd + n] for _lg, pd, n in plan.feat_segs()], axis=-1)


def _pallas_apply(plan: _StackPlan, x, ws, bs):
    o = _pallas_forward(plan, _pad_x(plan, x),
                        tuple(_pad_w(plan, i, w) for i, w in enumerate(ws)),
                        tuple(_pad_b(plan, b) for b in bs))
    return _unpad_feat(plan, o, x.shape[0])


def _pallas_grad(plan: _StackPlan, x, ws, bs, g):
    m = x.shape[0]
    dxp, dwps, dbps = _pallas_backward(
        plan, _pad_x(plan, x), _pad_feat(plan, g),
        tuple(_pad_w(plan, i, w) for i, w in enumerate(ws)),
        tuple(_pad_b(plan, b) for b in bs))
    dx = dxp[:m, :plan.d0]
    dws = tuple(_unpad_dw(plan, i, dwp).astype(ws[i].dtype)
                for i, dwp in enumerate(dwps))
    dbs = tuple(dbp[0, :plan.u].astype(bs[i].dtype)
                for i, dbp in enumerate(dbps))
    return dx, dws, dbs


# ---------------------------------------------------------------- entry point

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stack_core(plan: _StackPlan, x, ws, bs):
    if plan.impl == "pallas":
        return _pallas_apply(plan, x, ws, bs)
    return _xla_forward(plan, x, ws, bs)[0]


def _stack_core_fwd(plan, x, ws, bs):
    if plan.impl == "pallas":
        return _pallas_apply(plan, x, ws, bs), (x, ws, bs)
    feat, zs = _xla_forward(plan, x, ws, bs)
    if plan.remat:
        return feat, (x, ws, bs)
    if plan.connectivity == "densenet":   # feat IS the stream buffer
        return feat, (feat, ws, tuple(zs))
    return feat, (x, ws, tuple(zs))


def _stack_core_bwd(plan, res, g):
    if plan.impl == "pallas":
        x, ws, bs = res
        return _pallas_grad(plan, x, ws, bs, g)
    buf = None
    if plan.remat:
        x, ws, bs = res
        zs = _xla_forward(plan, x, ws, bs)[1]
    else:
        x, ws, zs = res
        if plan.connectivity == "densenet":
            buf, x = res[0], res[0][:, :plan.d0]
    dx, dws, dbs = _xla_backward(plan, x, ws, list(zs), g, buf)
    return dx, tuple(dws), tuple(dbs)


_stack_core.defvjp(_stack_core_fwd, _stack_core_bwd)


def dense_stack(x: jax.Array, ws: Sequence[jax.Array],
                bs: Sequence[jax.Array], *, connectivity: str = "densenet",
                activation: str = "swish", impl: Optional[str] = None,
                interpret: Optional[bool] = None, remat: bool = False,
                block_m: int = 128) -> jax.Array:
    """Feature of the L-layer stack, differentiable through the custom VJP.

    ``impl=None`` auto-selects: the Pallas kernels on TPU, the XLA streaming
    twin elsewhere. Returns the penultimate feature exactly as
    ``mlp_block_apply`` does (full stream for densenet, last hidden
    otherwise); tolerances vs the jnp loop are float32 reassociation only.
    """
    if connectivity not in FUSED_CONNECTIVITIES:
        raise ValueError(f"connectivity {connectivity!r} not fused; "
                         f"have {FUSED_CONNECTIVITIES}")
    _act_pair(activation)   # validates
    if not ws:
        raise ValueError("dense_stack needs at least one layer")
    if impl is None:
        impl = "pallas" if mosaic_available() else "xla"
    if impl not in ("xla", "pallas"):
        raise ValueError(impl)
    plan = _StackPlan(connectivity, activation, len(ws), x.shape[-1],
                      ws[0].shape[-1], impl, default_interpret(interpret),
                      bool(remat), block_m)
    lead = x.shape[:-1]
    out = _stack_core(plan, x.reshape((-1, plan.d0)), tuple(ws), tuple(bs))
    return out.reshape(lead + (plan.feat_dim,))
