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
