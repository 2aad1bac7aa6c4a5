"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.bitmap_spmm import (STRIP_VMEM_BUDGET, bitmap_spmm,
                                       bitmap_spmm_grouped, grid_steps,
                                       hbm_traffic_model, row_blocks,
                                       strip_tiles)
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


@pytest.mark.parametrize("m", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_serving_head_shape(m, dtype):
    """The serving engine's LM-head tile (BK != BN, tiny decode batch):
    interpret-mode kernel == dense reference."""
    r = np.random.default_rng(7)
    k, n = 64, 256
    w = r.standard_normal((k, n)).astype(np.float32)
    w *= r.random((k, n)) >= 0.6
    bw = pack_bitmap(w.astype(dtype), block=(64, 128))
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    out = bitmap_spmm(x, bw, interpret=True)
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
    """Decode-shaped M (1..128, not a multiple of 128): the row block is
    M rounded up to the sublane multiple, not padded to 128 rows, and
    interpret-mode output equals the dense reference exactly in shape and
    numerically in value."""
    r = np.random.default_rng(m)
    n = 256
    w = r.standard_normal((k, n)).astype(np.float32)
    w *= r.random((k, n)) >= 0.7
    bw = pack_bitmap(w.astype(dtype), block=(min(k, 128), 128))
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    sub = 16 if dtype == jnp.bfloat16 else 8
    assert row_blocks(bw, m, dtype) == (-(-m // sub) * sub, 1)
    out = bitmap_spmm(x, bw, interpret=True)
    assert out.shape == (m, n)
    expect = ref.bitmap_spmm_ref(x, bw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


def _strip_bytes(tk, bk, nw, q, bn, bm, xs, vs):
    """Hand count of a strip's double-buffered blocks, VMEM-padded, with
    the row block's f32 accumulator and double-buffered output (at
    f32)."""
    sub = {2: 16, 1: 32}.get(vs, 8)
    return (2 * tk * (bm * bk * xs + -(-nw // 8) * 8 * bn * 4
                      + -(-q // sub) * sub * bn * vs)
            + 3 * bm * bn * 4)


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
    # the chunked prefill's 16 slots x 32 rows in one row block: down's
    # whole-K x block (16 MiB double-buffered) does not fit, 4 strips do
    (2048, 34, 512, 16), (8192, 34, 512, 16),
])
def test_strip_tiles_at_olmo_widths(k, q, bm, tk):
    """olmo-1b's bf16 matrices at 75 % (Q = 34 value rows), decode and
    prefill rows: every K is one strip but down's at prefill."""
    assert strip_tiles(k // 128, 128, 4, q, 128, bm, 2, 2) == tk


@pytest.mark.parametrize("bm,q", [(16, 34), (128, 128), (4096, 128),
                                  (8192, 128), (32768, 128)])
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
    # prefill rows: the 16 x 32 rows in one row block, K in one strip
    assert grid_steps(mats[0], 512, jnp.bfloat16) == 16 * 16


def _abstract_weight(k, n, q, lead=()):
    return BitmapWeight(
        packed_bits=jax.ShapeDtypeStruct(
            lead + (k // 128, n // 128, 4, 128), jnp.int32),
        values=jax.ShapeDtypeStruct(
            lead + (k // 128, n // 128, q, 128), jnp.bfloat16),
        shape=(k, n), block=(128, 128))


@pytest.mark.parametrize("rows", [16, 512])
def test_grid_steps_hand_count_at_granite_widths(rows):
    """One granite-3.0-3b-a800m step (32 layers; q/o 1536 -> 1536, k/v
    1536 -> 512; 40 experts of gate/up 1536 -> 512 and down 512 -> 1536)
    at 16 decode rows and at the 512 rows of a prefill call (each
    expert's buffer holds one row per token): one row block, every K one
    strip, so the count is the same."""
    p, e = 32, 40
    attn = [_abstract_weight(1536, 1536, 34, (p,)),
            _abstract_weight(1536, 512, 34, (p,)),
            _abstract_weight(1536, 512, 34, (p,)),
            _abstract_weight(1536, 1536, 34, (p,))]
    experts = [_abstract_weight(1536, 512, 34, (p, e)),
               _abstract_weight(1536, 512, 34, (p, e)),
               _abstract_weight(512, 1536, 34, (p, e))]
    for w in attn + experts:
        assert row_blocks(w, rows, jnp.bfloat16) == (rows, 1)
    assert sum(grid_steps(w, rows, jnp.bfloat16)
               for w in attn + experts) \
        == p * ((12 + 4 + 4 + 12) + e * (4 + 4 + 12)) == 26624


@pytest.mark.parametrize("m,rows", [(1, 16), (16, 16), (20, 32),
                                    (128, 128), (130, 144), (384, 384),
                                    (512, 512)])
def test_row_blocks_take_all_of_m_while_it_fits(m, rows):
    """At olmo-1b's widths (bf16, Q = 34) the row block is M rounded up
    to 16 rows — decode's 16 slots and a prefill call's 512 rows alike —
    so each tile is expanded once per call."""
    for k in (2048, 8192):
        assert row_blocks(_abstract_weight(k, 2048, 34), m,
                          jnp.bfloat16) == (rows, 1)


def test_row_blocks_split_m_into_the_fewest_equal_blocks_that_fit():
    """An M whose one-tile blocks overflow the VMEM budget splits into
    the fewest equal row blocks whose blocks fit."""
    w = _abstract_weight(2048, 2048, 34)
    per_row = 2 * 128 * 2 + 3 * 128 * 4       # x, accumulator, output
    most = (STRIP_VMEM_BUDGET - 2 * (8 * 128 * 4 + 48 * 128 * 2)) // per_row
    m = 2 * most + 1
    rows, blocks = row_blocks(w, m, jnp.bfloat16)
    assert blocks == 3 and rows * blocks >= m and rows <= most
    assert rows % 16 == 0 and rows * (blocks - 1) < m


@pytest.mark.parametrize("m,k,g", [
    # a prefill call's 512 rows through olmo-1b's K, and granite's
    # grouped expert widths (K = 1536, KT = 12); an M of 3 x 128
    pytest.param(512, 2048, 1, id="512-k2048"),
    pytest.param(512, 1536, 3, id="512-grouped-k1536"),
    pytest.param(384, 1536, 1, id="384-k1536"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_prefill_rows_in_one_row_block(m, k, g, dtype):
    """Prefill-shaped M runs as one row block (each tile expanded once,
    applied to every row): interpret-mode output equals the dense
    reference."""
    r = np.random.default_rng(m + k + g)
    n = 256
    w = r.standard_normal((g, k, n)).astype(np.float32)
    w *= r.random((g, k, n)) >= 0.75
    bw = pack_bitmap_experts(w[None].astype(dtype), block=(128, 128))
    bw = BitmapWeight(packed_bits=bw.packed_bits[0], values=bw.values[0],
                      shape=bw.shape, block=bw.block)
    x = jnp.asarray(r.standard_normal((g, m, k)), dtype)
    assert row_blocks(bw, m, dtype) == (m, 1)
    out = bitmap_spmm_grouped(x, bw, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.bitmap_spmm_grouped_ref(x, bw), np.float32),
        atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_rows_past_one_row_block(dtype):
    """An M too large for one row block runs as equal row blocks, the
    last padded: interpret-mode output equals the dense reference."""
    r = np.random.default_rng(3)
    k = n = 128
    w = r.standard_normal((k, n)).astype(np.float32)
    w *= r.random((k, n)) >= 0.75
    bw = pack_bitmap(w.astype(dtype), block=(128, 128))
    m = next(m for m in range(4096, 1 << 20, 4096)
             if row_blocks(bw, m, dtype)[1] > 1) + 8
    rows, blocks = row_blocks(bw, m, dtype)
    assert blocks >= 2 and rows * blocks > m
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    out = bitmap_spmm(x, bw, interpret=True)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.bitmap_spmm_ref(x, bw),
                                          np.float32),
                               atol=_tol(dtype) * np.sqrt(k), rtol=1e-2)


def test_bitmap_spmm_m_not_multiple_of_bm():
    """M between 128 and 256 that 128 does not divide still works (one
    row block of M padded to the sublane multiple, sliced back)."""
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
