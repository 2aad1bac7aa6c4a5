"""Bitmap / block-sparse weight containers: roundtrip + budget + traffic."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.sparse import (pack_bitmap, pack_block_sparse, unpack_bitmap,
                          unpack_block_sparse)
from repro.sparse.pruning import per_tensor_prune, sparsity_of
import jax.numpy as jnp


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1), st.floats(0.1, 0.95))
def test_bitmap_roundtrip(seed, sparsity):
    r = np.random.default_rng(seed)
    w = r.standard_normal((128, 256)).astype(np.float32)
    w *= r.random((128, 256)) >= sparsity
    bw = pack_bitmap(w, block=(64, 64))
    np.testing.assert_array_equal(np.asarray(unpack_bitmap(bw)), w)
    # compression beats dense once sparsity clears the bitmap overhead
    if sparsity > 0.3:
        assert bw.compression > 1.0


def test_bitmap_budget_reprune():
    """Tiles denser than the budget are re-pruned to top magnitudes."""
    r = np.random.default_rng(0)
    w = r.standard_normal((64, 64)).astype(np.float32)  # fully dense
    bw = pack_bitmap(w, block=(64, 64), density_budget=0.25)
    dense = np.asarray(unpack_bitmap(bw))
    kept = dense != 0
    assert kept.sum() <= int(np.ceil(0.25 * 64 * 64))
    # kept entries are exactly the largest |w|
    thresh = np.abs(dense[kept]).min()
    assert (np.abs(w[~kept]) <= thresh + 1e-6).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1), st.floats(0.2, 0.9))
def test_block_sparse_roundtrip(seed, p_zero):
    r = np.random.default_rng(seed)
    kt, nt, bk, bn = 4, 3, 32, 32
    w = r.standard_normal((kt * bk, nt * bn)).astype(np.float32)
    mask = r.random((kt, nt)) >= p_zero
    w = (w.reshape(kt, bk, nt, bn)
         * mask[:, None, :, None]).reshape(kt * bk, nt * bn)
    bw = pack_block_sparse(w, block=(bk, bn))
    np.testing.assert_array_equal(np.asarray(unpack_block_sparse(bw)), w)
    assert abs(bw.density - mask.mean()) < 1e-9


def test_per_tensor_prune_exact():
    r = np.random.default_rng(1)
    w = jnp.asarray(r.standard_normal((64, 64)), jnp.float32)
    pruned = per_tensor_prune(w, 0.75)
    frac = float((pruned == 0).mean())
    assert abs(frac - 0.75) < 0.01
    assert sparsity_of({"w": pruned}) == frac


@pytest.mark.parametrize("sparsity", [0.3333, 0.5, 0.75, 0.999])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_global_threshold_is_the_concatenated_quantile(sparsity, dtype):
    """The global L1 threshold found without concatenating the leaves is
    the very float32 number ``jnp.quantile`` gives over their
    concatenation."""
    from repro.sparse.pruning import global_threshold
    r = np.random.default_rng(7)
    leaves = [jnp.asarray(r.standard_normal(s), dtype)
              for s in [(33, 17), (64, 8), (5, 103)]]
    want = jnp.quantile(jnp.concatenate(
        [jnp.abs(l).reshape(-1) for l in leaves]).astype(jnp.float32),
        sparsity)
    assert float(global_threshold(leaves, sparsity)) == float(want)


def test_global_prune_counts_past_int32():
    """granite-3.0-3b-a800m at its published 32 layers holds 3,223,191,552
    projection weights (3,223,289,856 leaves the prune counts, with the
    period-stacked norm scales), past 2**31 - 1: the quantile's ranks are
    host ints in its float32 arithmetic, and the whole prune traces at
    that size (shapes only, nothing allocated)."""
    import jax
    from repro.configs import get_config
    from repro.models.model import param_structs
    from repro.sparse.pruning import _is_prunable, _position, global_l1_prune

    params = param_structs(get_config("granite-moe-3b-a800m"))
    counted = [(jax.tree_util.keystr(p), int(np.prod(l.shape)))
               for p, l in jax.tree_util.tree_leaves_with_path(params)
               if _is_prunable(p, l, None)]
    assert sum(s for p, s in counted if "norm" not in p) == 3_223_191_552
    n = sum(s for _, s in counted)
    pos = np.float32(0.75) * (np.float32(n) - np.float32(1))
    low, high = _position(0.75, n)
    assert (low, high) == (int(np.floor(pos)), int(np.ceil(pos)))
    assert 2 ** 31 - 1 < low <= high < n
    # float32 rounds n - 1 up to n at these counts: the rank stays an index
    assert _position(1.0, 3_223_191_552) == (3_223_191_551,) * 2

    out = jax.eval_shape(lambda p: global_l1_prune(p, 0.75), params)
    assert jax.tree.map(lambda s: (s.shape, s.dtype), out) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), params)


def test_global_prune_donates_and_zeroes_at_or_below_the_threshold():
    """Each prunable leaf is donated to its pruned copy (the caller's leaf
    is consumed), and the copy is ``where(|w| <= threshold, 0, w)`` with
    the global threshold, computed here on a copy; other leaves pass
    through untouched."""
    import jax
    from repro.sparse.pruning import global_l1_prune, global_threshold
    r = np.random.default_rng(3)
    params = {
        "embed": jnp.asarray(r.standard_normal((16, 8)), jnp.bfloat16),
        "a": jnp.asarray(r.standard_normal((4, 32, 16)), jnp.bfloat16),
        "b": jnp.asarray(r.standard_normal((16, 24)), jnp.bfloat16),
        "norm": jnp.asarray(r.standard_normal((8,)), jnp.bfloat16)}
    copy = jax.tree.map(jnp.copy, params)
    thresh = global_threshold([copy["a"], copy["b"]], 0.75)
    got = global_l1_prune(params, 0.75)
    assert params["a"].is_deleted() and params["b"].is_deleted()
    for k in ("a", "b"):
        want = jnp.where(jnp.abs(copy[k]) <= thresh, 0, copy[k])
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want))
    assert float(jnp.mean(got["a"] == 0)) > 0.5
    for k in ("embed", "norm"):
        assert got[k] is params[k]
