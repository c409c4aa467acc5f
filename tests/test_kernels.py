"""Per-kernel allclose sweeps vs the pure-jnp/numpy oracles (interpret mode).

Every kernel is swept over shapes AND dtypes per the deliverable; blocks are
deliberately smaller than the arrays so the grid logic is exercised.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dense_block.dense_block import fused_dense
from repro.kernels.dense_block.ops import dense_concat_matmul, fused_dense_padded
from repro.kernels.dense_block.ref import dense_concat_matmul_ref, fused_dense_ref
from repro.kernels.flash_attention.ops import gqa_flash
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.replay_tree import ops as rt_ops
from repro.kernels.replay_tree import ref as rt_ref
from repro.kernels.replay_tree.replay_tree import (tree_sample, tree_set,
                                                   tree_set_onehot)
from repro.kernels.ssd_scan.ops import ssd_chunked_kernel
from repro.kernels.ssd_scan.ssd_scan import ssd_chunk_dual
from repro.kernels.ssd_scan.ref import ssd_chunk_dual_ref
from repro.kernels.ssd_scan.ref import ssd_chunked


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- dense_block

@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (64, 128, 32), (128, 256, 128),
                                   (32, 96, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", ["swish", "identity"])
def test_fused_dense_matches_ref(m, k, n, dtype, activation):
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = jax.random.normal(ks[1], (k, n), dtype) * 0.1
    b = jax.random.normal(ks[2], (n,), dtype)
    out = fused_dense_padded(x, w, b, activation=activation,
                             bm=16, bn=16, bk=16)
    ref = fused_dense_ref(x, w, b, activation)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("widths", [(8, 16), (24, 16, 40), (128,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_concat_matmul_densenet_layer(widths, dtype):
    """The paper's DenseNet layer: concat never materializes."""
    key = jax.random.key(1)
    parts = [jax.random.normal(jax.random.fold_in(key, i), (32, wd), dtype)
             for i, wd in enumerate(widths)]
    k = sum(widths)
    w = jax.random.normal(jax.random.fold_in(key, 99), (k, 48), dtype) * 0.1
    b = jnp.zeros((48,), dtype)
    out = dense_concat_matmul(parts, w, b, activation="swish")
    ref = dense_concat_matmul_ref(parts, w, b, "swish")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_fused_dense_exact_blocks():
    """No-padding path with multiple K blocks (accumulator reuse)."""
    x = jax.random.normal(jax.random.key(2), (128, 384))
    w = jax.random.normal(jax.random.key(3), (384, 128)) * 0.05
    out = fused_dense(x, w, None, activation="swish", bm=64, bn=64, bk=128)
    ref = fused_dense_ref(x, w, None, "swish")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ flash_attention

@pytest.mark.parametrize("sq,skv,d,bq,bkv", [
    (128, 128, 32, 64, 64), (256, 256, 64, 64, 128), (128, 256, 16, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(sq, skv, d, bq, bkv, dtype, causal):
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (3, sq, d), dtype)
    k = jax.random.normal(ks[1], (3, skv, d), dtype)
    v = jax.random.normal(ks[2], (3, skv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (2, 128, 32))
    k = jax.random.normal(ks[1], (2, 128, 32))
    v = jax.random.normal(ks[2], (2, 128, 32))
    out = flash_attention(q, k, v, causal=True, window=window, bq=32, bkv=32)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_softcap_gemma2():
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (2, 64, 32)) * 3
    k = jax.random.normal(ks[1], (2, 64, 32)) * 3
    v = jax.random.normal(ks[2], (2, 64, 32))
    out = flash_attention(q, k, v, causal=True, softcap=50.0, bq=32, bkv=32)
    ref = attention_ref(q, k, v, causal=True, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_gqa_flash_wrapper_matches_model_attention():
    from repro.kernels.flash_attention.ref import plain_attention
    ks = jax.random.split(jax.random.key(7), 3)
    B, S, H, KV, hd = 2, 128, 8, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = gqa_flash(q, k, v, causal=True, bq=64, bkv=64)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------- ssd_scan

@pytest.mark.parametrize("g,h,q,n,p", [(2, 2, 16, 8, 8), (1, 3, 32, 16, 8),
                                       (4, 1, 8, 4, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_dual_matches_ref(g, h, q, n, p, dtype):
    ks = jax.random.split(jax.random.key(8), 7)
    c = jax.random.normal(ks[0], (g, q, n), dtype)
    b = jax.random.normal(ks[1], (g, q, n), dtype)
    x = jax.random.normal(ks[2], (g, h, q, p), dtype)
    lg = -jax.nn.softplus(jax.random.normal(ks[3], (g, h, q)))
    cum = jnp.cumsum(lg, axis=-1)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (g, h, q)))
    state = jax.random.normal(ks[5], (g, h, p, n), jnp.float32)
    dskip = jax.random.normal(ks[6], (h,), jnp.float32)
    out = ssd_chunk_dual(c, b, x, cum, dt, state, dskip)
    ref = ssd_chunk_dual_ref(c, b, x, cum, dt, state, dskip)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, **_tol(dtype))


@pytest.mark.parametrize("chunk", [8, 16])
def test_ssd_chunked_kernel_matches_models_ssm(chunk):
    """Kernel pipeline == the model's pure-jnp ssd_chunked (+ D skip)."""
    ks = jax.random.split(jax.random.key(9), 6)
    B, S, H, P, N = 2, 32, 2, 8, 4
    x = jax.random.normal(ks[0], (B, S, H, P))
    b = jax.random.normal(ks[1], (B, S, N))
    c = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    log_a = jax.random.normal(ks[4], (H,)) * 0.3
    d_skip = jax.random.normal(ks[5], (H,))
    y_k, f_k = ssd_chunked_kernel(x, b, c, dt, log_a, d_skip, chunk=chunk)
    y_m, f_m = ssd_chunked(x, b, c, dt, log_a, chunk=chunk)
    y_m = y_m + d_skip[None, None, :, None] * x
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_m),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- replay_tree

@pytest.mark.parametrize("capacity", [5, 37, 64, 200])
def test_replay_tree_set_kernel_matches_ref(capacity):
    """Pallas scatter+resum == jnp oracle, incl. partial second update."""
    rng = np.random.default_rng(10)
    pr = jnp.asarray(rng.uniform(0.1, 5.0, capacity), jnp.float32)
    idx = jnp.arange(capacity)
    t_k = tree_set(rt_ref.tree_init_ref(capacity), idx, pr)
    t_r = rt_ref.tree_set_ref(rt_ref.tree_init_ref(capacity), idx, pr)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_r), rtol=1e-6)
    sub = jnp.asarray(rng.integers(0, capacity, 7))
    val = jnp.asarray(rng.uniform(0.1, 9.0, 7), jnp.float32)
    np.testing.assert_allclose(np.asarray(tree_set(t_k, sub, val)),
                               np.asarray(rt_ref.tree_set_ref(t_r, sub, val)),
                               rtol=1e-6)


@pytest.mark.parametrize("capacity,bt,fill", [
    (37, 16, "uniform"), (128, 64, "uniform"), (1000, 128, "uniform"),
    (20000, 128, "uniform"), (100_000, 128, "uniform"),
    (250_000, 128, "uniform"), (1_000_000, 128, "uniform"),
    (1_000_000, 128, "warmup")])
def test_replay_tree_sample_kernel_matches_ref(capacity, bt, fill):
    """Capacity 20000 puts the leaf level in 256 rows of 128 nodes: the
    one-hot-matmul row lookup path, not just the VPU row selects. From
    capacity 100 000 (depth 18) on, the levels below the top stage come
    from rows fetched by DMA: one row level at 100 000, two at 250 000 (a
    four-shard slice of 1e6), four at 1e6. ``warmup`` is a replay after its
    warm-up: 10 048 rows at the initial priority, then the zero tail.
    Targets are stratified as the replay draws them, plus ``total`` itself
    (which walks into the zero tail and is clamped); in ``warmup`` the
    first 16 are the integers 1..16, each equal to a left mass on the leaf
    level or one of the four above it (``t >= lmass`` goes right there)."""
    rng = np.random.default_rng(11)
    if fill == "warmup":
        n = 10_048
        pr = jnp.ones((n,), jnp.float32)
    else:
        n = capacity
        pr = jnp.asarray(rng.uniform(0.0, 3.0, capacity), jnp.float32)
    tree = rt_ref.tree_set_ref(rt_ref.tree_init_ref(capacity),
                               jnp.arange(n), pr)
    total = rt_ref.tree_total_ref(tree)
    b = 2 * bt
    u = jnp.asarray(rng.uniform(0.0, 1.0, b), jnp.float32)
    targets = (jnp.arange(b, dtype=jnp.float32) + u) * (total / b)
    targets = targets.at[-1].set(total)
    if fill == "warmup":
        targets = targets.at[:16].set(jnp.arange(1, 17, dtype=jnp.float32))
    leaf_k, pri_k = tree_sample(tree, targets, capacity=capacity, bt=bt)
    leaf_r = rt_ref.tree_sample_ref(tree, targets, capacity=capacity)
    np.testing.assert_array_equal(np.asarray(leaf_k), np.asarray(leaf_r))
    np.testing.assert_array_equal(
        np.asarray(pri_k), np.asarray(rt_ref.tree_get_ref(tree, leaf_r)))
    if fill == "warmup":                # total walks the zero tail's right edge
        assert int(leaf_k[-1]) == capacity - 1


@pytest.mark.parametrize("capacity,chunk", [(5, 1024), (37, 1024), (64, 16),
                                            (200, 1024), (3000, 1024),
                                            (20000, 1 << 14)])
def test_replay_tree_set_onehot_matches_ref(capacity, chunk):
    """The TPU-lowerable scatter-free tree_set == jnp oracle; capacity 20000
    (leaf level 256 rows of 128) with chunk 2**14 (128 rows per one-hot
    matmul) exercises the blocked matmul path on wide levels."""
    rng = np.random.default_rng(13)
    # every leaf of a small tree; 2048 distinct leaves of a wide one (the
    # kernel's duplicate mask is (n, n))
    idx = (np.arange(capacity) if capacity <= 4096
           else rng.choice(capacity, 2048, replace=False))
    pr = jnp.asarray(rng.uniform(0.1, 5.0, idx.size), jnp.float32)
    idx = jnp.asarray(idx)
    t_k = tree_set_onehot(rt_ref.tree_init_ref(capacity), idx, pr,
                          chunk=chunk)
    t_r = rt_ref.tree_set_ref(rt_ref.tree_init_ref(capacity), idx, pr)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_r), rtol=1e-5,
                               atol=1e-6)
    sub = jnp.asarray(rng.integers(0, capacity, 9))
    val = jnp.asarray(rng.uniform(0.1, 9.0, 9), jnp.float32)
    np.testing.assert_allclose(np.asarray(tree_set_onehot(t_k, sub, val,
                                                          chunk=chunk)),
                               np.asarray(rt_ref.tree_set_ref(t_r, sub, val)),
                               rtol=1e-5, atol=1e-6)


def test_replay_tree_set_onehot_duplicate_keep_last():
    """Duplicate leaf writes resolve keep-last, the host SumTree semantic."""
    capacity = 11
    base = rt_ref.tree_set_ref(rt_ref.tree_init_ref(capacity),
                               jnp.arange(capacity),
                               jnp.ones((capacity,), jnp.float32))
    idx = jnp.asarray([3, 7, 3, 7, 3], jnp.int32)
    val = jnp.asarray([10.0, 20.0, 30.0, 40.0, 50.0], jnp.float32)
    tree = tree_set_onehot(base, idx, val)
    leaves = np.asarray(rt_ref.tree_get_ref(tree, jnp.arange(capacity)))
    assert leaves[3] == 50.0 and leaves[7] == 40.0
    expect_total = capacity - 2 + 50.0 + 40.0
    np.testing.assert_allclose(float(rt_ref.tree_total_ref(tree)),
                               expect_total, rtol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_replay_tree_ops_match_host_sumtree(backend):
    """Both dispatch backends agree with the NumPy SumTree end to end."""
    from repro.rl.replay import SumTree
    capacity = 73
    rng = np.random.default_rng(12)
    pr = rng.uniform(0.05, 4.0, capacity).astype(np.float32)
    host = SumTree(capacity)
    host.set(np.arange(capacity), pr)
    tree = rt_ops.sumtree_set(rt_ops.sumtree_init(capacity),
                              jnp.arange(capacity), jnp.asarray(pr),
                              backend=backend)
    np.testing.assert_allclose(float(rt_ops.sumtree_total(tree)), host.total,
                               rtol=1e-5)
    targets = rng.uniform(0, host.total, 300)
    leaf, _ = rt_ops.sumtree_sample(tree, jnp.asarray(targets, jnp.float32),
                                    capacity=capacity, backend=backend)
    host_leaf = host.sample(targets)
    assert (np.asarray(leaf) == host_leaf).mean() > 0.99   # float32 vs 64
    assert np.asarray(leaf).min() >= 0 and np.asarray(leaf).max() < capacity


def test_replay_tree_pallas_interpret_off_runs_off_tpu():
    """backend='pallas', interpret=False off-TPU raises at BOTH the set and
    sample sites: real lowering needs Mosaic, and quietly running the XLA
    reference instead would hide that the kernel never ran."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU path")
    capacity = 41
    tree = rt_ops.sumtree_set(rt_ops.sumtree_init(capacity),
                              jnp.arange(capacity),
                              jnp.ones((capacity,), jnp.float32))
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        rt_ops.sumtree_set(tree, jnp.arange(capacity),
                           jnp.ones((capacity,), jnp.float32),
                           backend="pallas", interpret=False)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        rt_ops.sumtree_sample(tree, jnp.zeros((64,), jnp.float32),
                              capacity=capacity, backend="pallas",
                              interpret=False)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_replay_tree_sample_edge_targets_clamped(backend):
    """target == total (and beyond) stays inside [0, capacity)."""
    capacity = 5
    tree = rt_ops.sumtree_set(rt_ops.sumtree_init(capacity),
                              jnp.arange(capacity),
                              jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0]))
    total = float(rt_ops.sumtree_total(tree))
    leaf, _ = rt_ops.sumtree_sample(
        tree, jnp.asarray([total, total * 2.0, 0.0], jnp.float32),
        capacity=capacity, backend=backend)
    leaf = np.asarray(leaf)
    assert (leaf >= 0).all() and (leaf < capacity).all()
    assert leaf[0] == capacity - 1 and leaf[2] == 0
