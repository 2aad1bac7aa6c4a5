"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.bitmap_spmm import (STRIP_VMEM_BUDGET, bitmap_spmm,
                                       bitmap_spmm_grouped, grid_steps,
                                       hbm_traffic_model, strip_tiles)
from repro.kernels.block_sparse import block_sparse_matmul
from repro.kernels.flash_attention import flash_attention
from repro.sparse import pack_bitmap, pack_block_sparse
from repro.sparse.format import (BitmapWeight, pack_bitmap_experts,
                                 shard_bitmap)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-3


def _sweep_case(m, k, n, block, layout="plain", id=None):
    return pytest.param(m, k, n, block, layout, id=id or
                        f"{m}-{k}-{n}-{layout}")


@pytest.mark.parametrize("m,k,n,block,layout", [
    _sweep_case(128, 128, 128, (128, 128), id="128-128-128-block0"),
    _sweep_case(128, 256, 256, (128, 128), id="128-256-256-block1"),
    _sweep_case(256, 128, 256, (64, 128), id="256-128-256-block2"),
    _sweep_case(128, 384, 128, (128, 64), id="128-384-128-block3"),
    # strips: KT = 16 and 64 in one strip (KT = 64 in f32 takes two),
    # KT = 7 (prime)
    _sweep_case(128, 2048, 128, (128, 128)),
    _sweep_case(128, 8192, 128, (128, 128)),
    _sweep_case(128, 896, 128, (128, 128)),
    # three groups in one launch; row and column shards (KT = 8 each)
    _sweep_case(16, 2048, 256, (128, 128), "grouped"),
    _sweep_case(16, 2048, 256, (128, 128), "row"),
    _sweep_case(16, 2048, 256, (128, 128), "col"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sparsity", [0.5, 0.75, 0.95])
def test_bitmap_spmm_sweep(m, k, n, block, layout, dtype, sparsity):
    r = np.random.default_rng(hash((m, k, n, sparsity)) % 2**32)
    g = 3 if layout == "grouped" else 1
    w = r.standard_normal((g, k, n)).astype(np.float32)
    w *= r.random((g, k, n)) >= sparsity
    x = jnp.asarray(r.standard_normal((g, m, k)), dtype)
    if layout == "grouped":
        bw = pack_bitmap_experts(w[None].astype(dtype), block=block)
        bw = BitmapWeight(packed_bits=bw.packed_bits[0],
                          values=bw.values[0], shape=bw.shape,
                          block=bw.block)
        out = bitmap_spmm_grouped(x, bw, interpret=True)
        expect = ref.bitmap_spmm_grouped_ref(x, bw)
    else:
        x, bw = x[0], pack_bitmap(w[0].astype(dtype), block=block)
        expect = ref.bitmap_spmm_ref(x, bw)
        if layout == "plain":
            out = bitmap_spmm(x, bw, interpret=True)
        else:
            out = ops.bitmap_spmm(x, shard_bitmap(bw, 2, layout),
                                  impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


@pytest.mark.parametrize("m,k,n,block,p_zero", [
    (128, 256, 256, (128, 128), 0.5),
    (128, 512, 128, (128, 128), 0.75),
    (256, 256, 256, (64, 64), 0.3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_sparse_sweep(m, k, n, block, p_zero, dtype):
    r = np.random.default_rng(hash((m, k, n, p_zero)) % 2**32)
    kt, nt = k // block[0], n // block[1]
    w = r.standard_normal((k, n)).astype(np.float32)
    mask = r.random((kt, nt)) >= p_zero
    w = (w.reshape(kt, block[0], nt, block[1])
         * mask[:, None, :, None]).reshape(k, n)
    bw = pack_block_sparse(jnp.asarray(w, dtype), block=block)
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    out = block_sparse_matmul(x, bw, interpret=True)
    expect = ref.block_sparse_matmul_ref(x, bw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


@pytest.mark.parametrize("hq,hkv,s,d,window", [
    (4, 4, 128, 64, None),
    (4, 2, 256, 64, None),
    (8, 1, 128, 128, None),
    (4, 2, 256, 64, 64),
    (2, 2, 128, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(hq, hkv, s, d, window, dtype):
    r = np.random.default_rng(hash((hq, s, d, window or 0)) % 2**32)
    q = jnp.asarray(r.standard_normal((2, hq, s, d)), dtype)
    k = jnp.asarray(r.standard_normal((2, hkv, s, d)), dtype)
    v = jnp.asarray(r.standard_normal((2, hkv, s, d)), dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          bq=64, bkv=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 2e-3)


@pytest.mark.parametrize("m,bm", [(4, 4), (8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_serving_head_shape(m, bm, dtype):
    """The serving engine's LM-head tile (BK != BN, tiny decode batch):
    interpret-mode kernel == dense reference."""
    r = np.random.default_rng(7)
    k, n = 64, 256
    w = r.standard_normal((k, n)).astype(np.float32)
    w *= r.random((k, n)) >= 0.6
    bw = pack_bitmap(w.astype(dtype), block=(64, 128))
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    out = bitmap_spmm(x, bw, bm=bm, interpret=True)
    expect = ref.bitmap_spmm_ref(x, bw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


@pytest.mark.parametrize("m,k", [
    pytest.param(1, 64, id="1"), pytest.param(4, 64, id="4"),
    pytest.param(12, 64, id="12"),
    # the cell's 16 decode slots, and an M that needs padding, over a
    # whole-K strip of 16 tiles
    pytest.param(16, 2048, id="16-k2048"),
    pytest.param(20, 2048, id="20-k2048"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_small_m_decode_path(m, k, dtype):
    """Decode-shaped M (1..bm, not a multiple of 128): the small-M path
    rounds the row tile to the sublane multiple instead of padding 32x,
    and interpret-mode output equals the dense reference exactly in
    shape and numerically in value."""
    r = np.random.default_rng(m)
    n = 256
    w = r.standard_normal((k, n)).astype(np.float32)
    w *= r.random((k, n)) >= 0.7
    bw = pack_bitmap(w.astype(dtype), block=(min(k, 128), 128))
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    out = bitmap_spmm(x, bw, interpret=True)   # default bm=128 > m
    assert out.shape == (m, n)
    expect = ref.bitmap_spmm_ref(x, bw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


def _strip_bytes(tk, bk, nw, q, bn, bm, xs, vs):
    """Hand count of a strip's double-buffered blocks, VMEM-padded."""
    sub = {2: 16, 1: 32}.get(vs, 8)
    return 2 * tk * (bm * bk * xs + -(-nw // 8) * 8 * bn * 4
                     + -(-q // sub) * sub * bn * vs)


STRIP_SHAPES = [  # kt, bk, nw, q, bn, bm, x itemsize, value itemsize
    (16, 128, 4, 34, 128, 16, 2, 2), (64, 128, 4, 34, 128, 128, 2, 2),
    (64, 128, 4, 66, 128, 128, 4, 4), (7, 128, 4, 128, 128, 128, 4, 4),
    (393, 128, 4, 128, 128, 128, 4, 4), (96, 128, 4, 128, 128, 256, 4, 4),
    (12, 128, 4, 13, 128, 16, 2, 2), (1, 64, 2, 33, 128, 16, 2, 2),
]


@pytest.mark.parametrize("shape", STRIP_SHAPES)
def test_strip_tiles_divides_kt_and_fits_the_budget(shape):
    tk = strip_tiles(*shape)
    assert shape[0] % tk == 0
    assert tk == 1 or _strip_bytes(tk, *shape[1:]) <= STRIP_VMEM_BUDGET
    # the largest such divisor
    assert not any(shape[0] % d == 0
                   and _strip_bytes(d, *shape[1:]) <= STRIP_VMEM_BUDGET
                   for d in range(tk + 1, shape[0] + 1))


@pytest.mark.parametrize("k,q,bm,tk", [
    (2048, 34, 16, 16), (2048, 34, 128, 16),      # q/k/v/o, gate, up
    (8192, 34, 16, 64), (8192, 34, 128, 64),      # down
    (2048, 128, 16, 16), (2048, 128, 128, 16),    # the dense-packed head
])
def test_strip_tiles_at_olmo_widths(k, q, bm, tk):
    """olmo-1b's bf16 matrices at 75 % (Q = 34 value rows), decode and
    prefill rows: every K is one strip."""
    assert strip_tiles(k // 128, 128, 4, q, 128, bm, 2, 2) == tk


@pytest.mark.parametrize("bm,q", [(16, 34), (128, 128), (4096, 128),
                                  (8192, 128)])
def test_strip_tiles_is_one_only_when_two_tiles_do_not_fit(bm, q):
    two = _strip_bytes(2, 128, 4, q, 128, bm, 4, 4)
    assert (strip_tiles(64, 128, 4, q, 128, bm, 4, 4) == 1) \
        == (two > STRIP_VMEM_BUDGET)


def test_grid_steps_hand_count_at_olmo_widths():
    """One olmo-1b decode step at 16 slots: per layer q/k/v/o 16 column
    blocks each, gate and up 64, down 16, each K one strip; the head 393
    (it held 16 x 393 one-tile steps before strips)."""
    def w(k, n, q, periods=16):
        lead = (periods,) if periods else ()
        return BitmapWeight(
            packed_bits=jax.ShapeDtypeStruct(
                lead + (k // 128, n // 128, 4, 128), jnp.int32),
            values=jax.ShapeDtypeStruct(
                lead + (k // 128, n // 128, q, 128), jnp.bfloat16),
            shape=(k, n), block=(128, 128))
    mats = [w(2048, 2048, 34)] * 4 + [w(2048, 8192, 34)] * 2 \
        + [w(8192, 2048, 34), w(2048, 50304, 128, periods=0)]
    assert sum(grid_steps(m, 16, jnp.bfloat16) for m in mats) \
        == 16 * (4 * 16 + 2 * 64 + 16) + 393
    # prefill rows: 4 row blocks of 128
    assert grid_steps(mats[0], 512, jnp.bfloat16) == 16 * 4 * 16


def test_bitmap_spmm_m_not_multiple_of_bm():
    """M between bm and 2*bm that bm does not divide still works (pad to
    the next row-block, slice back)."""
    r = np.random.default_rng(0)
    k, n, m = 64, 128, 130
    w = r.standard_normal((k, n)).astype(np.float32)
    w *= r.random((k, n)) >= 0.5
    bw = pack_bitmap(w, block=(64, 64))
    x = jnp.asarray(r.standard_normal((m, k)), jnp.float32)
    out = bitmap_spmm(x, bw, interpret=True)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.bitmap_spmm_ref(x, bw)),
                               atol=2e-3 * np.sqrt(k), rtol=1e-2)


def test_ops_bitmap_spmm_batched_activations():
    """The ops dispatcher accepts (..., K) activations (decode passes
    (B, 1, D)) on both impls."""
    from repro.kernels import ops
    r = np.random.default_rng(1)
    w = r.standard_normal((64, 128)).astype(np.float32)
    w *= r.random((64, 128)) >= 0.6
    bw = pack_bitmap(w, block=(64, 64))
    x = jnp.asarray(r.standard_normal((3, 1, 64)), jnp.float32)
    a = ops.bitmap_spmm(x, bw, impl="xla")
    b = ops.bitmap_spmm(x, bw, impl="pallas_interpret")
    assert a.shape == (3, 1, 128) and b.shape == (3, 1, 128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_hbm_traffic_model_shrinks_with_density():
    """Sparse HBM bytes < dense, and monotonically shrinking as the
    weight gets sparser (the paper's traffic-cut lever)."""
    r = np.random.default_rng(1)
    w0 = r.standard_normal((512, 512)).astype(np.float32)
    keep = r.random((512, 512))
    prev = None
    for sparsity in (0.5, 0.75, 0.9):
        bw = pack_bitmap(w0 * (keep >= sparsity), block=(128, 128))
        t = hbm_traffic_model((256, 512), bw)
        assert t["sparse_bytes"] < t["dense_bytes"]
        if prev is not None:
            assert t["sparse_bytes"] < prev
        prev = t["sparse_bytes"]


def test_hbm_traffic_model_reports_compression():
    r = np.random.default_rng(0)
    w = r.standard_normal((512, 512)).astype(np.float32)
    w *= r.random((512, 512)) >= 0.75
    bw = pack_bitmap(w, block=(128, 128))
    t = hbm_traffic_model((512, 512), bw)
    assert t["sparse_bytes"] < t["dense_bytes"]
    assert t["weight_compression"] > 2.0


def test_ops_dispatch_xla_path_matches():
    from repro.kernels import ops
    r = np.random.default_rng(0)
    w = r.standard_normal((128, 128)).astype(np.float32)
    w *= r.random((128, 128)) >= 0.6
    bw = pack_bitmap(w, block=(128, 128))
    x = jnp.asarray(r.standard_normal((128, 128)), jnp.float32)
    a = ops.bitmap_spmm(x, bw, impl="xla")
    b = ops.bitmap_spmm(x, bw, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)
