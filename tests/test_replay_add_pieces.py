"""The one-hot sum-tree set takes more leaves than one kernel call can (a
replay warm-up, ~10 000 rows) in pieces of ``_SET_ROWS``, in order: leaves and
ancestor sums come out as one keep-last set writes them, within rounding
(the kernel adds each leaf's delta to its old value)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.replay_tree import ref
from repro.kernels.replay_tree.replay_tree import _SET_ROWS, tree_set_onehot


@pytest.mark.parametrize("capacity", [1000, 20000])
@pytest.mark.parametrize("rows", [256, 600, 1300])
def test_add_in_pieces_matches_one_add(capacity, rows):
    """256 rows: one call; 600: two pieces and a remainder; 1300
    into 1 000 leaves: later pieces rewrite leaves of earlier ones. Capacity
    20 000 takes the one-hot matmul on its wide levels."""
    assert _SET_ROWS == 256
    rng = np.random.default_rng(rows)
    start = rng.uniform(0.1, 2.0, capacity).astype(np.float32)
    tree0 = ref.tree_set_ref(ref.tree_init_ref(capacity),
                             jnp.arange(capacity), jnp.asarray(start))
    # a ring buffer's rows from a cursor near its end, as an add writes them
    idx = (capacity - 100 + np.arange(rows)) % capacity
    val = rng.uniform(0.1, 5.0, rows).astype(np.float32)
    fn = lambda t, i, v: tree_set_onehot(t, i, v)
    args = (tree0, jnp.asarray(idx, jnp.int32), jnp.asarray(val))
    assert ("scan" in str(jax.make_jaxpr(fn)(*args))) == (rows > _SET_ROWS)
    got = np.asarray(jax.jit(fn)(*args))

    leaves = start.copy()
    for i, v in zip(idx, val):          # keep-last, one write at a time
        leaves[i] = v
    half = got.shape[0] // 2
    np.testing.assert_allclose(got[half:half + capacity], leaves, rtol=1e-6,
                               atol=1e-6)
    last = {int(i): k for k, i in enumerate(idx)}
    keep = np.asarray(sorted(last.values()))
    want = ref.tree_set_ref(tree0, jnp.asarray(idx[keep], jnp.int32),
                            jnp.asarray(val[keep]))
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-6)
