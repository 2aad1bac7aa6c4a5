"""The serving path's Pallas kernels compile for a TPU v5e.

Compiled for a described ``v5e:2x2`` topology (no chip attached): the
chip's own compiler refuses a block that breaks its tiling rules or an
operation it cannot lower, which interpret mode never checks.  Shapes are
olmo-1b's published projections and LM head at the decode slot count and
the prefill chunk, and granite-moe-3b-a800m's expert stacks through the
grouped kernel, granite-3.0-3b-a800m's whole decode step at its
published 32 layers, and olmo-1b's whole chunked-prefill step at the
benchmark cell's shapes.  Each test asserts the kernel is in the compiled
program as a ``tpu_custom_call``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitmap_spmm import bitmap_spmm, bitmap_spmm_grouped
from repro.sparse.format import WORD_ROWS, BitmapWeight

SLOTS, CHUNK = 4, 32                        # decode M, prefill M / slot
CELL_SLOTS = 16                 # the olmo-1b.gen benchmark cell's decode M
OLMO = {"wq": (2048, 2048), "wo": (2048, 2048), "w_up": (2048, 8192),
        "w_down": (8192, 2048), "head": (2048, 50304)}
GRANITE_EXPERTS, GRANITE_D, GRANITE_FF = 40, 1536, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # a described chip's executables cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _weight(k, n, chip, lead=(), sparsity=0.75, dtype=jnp.float32):
    """ShapeDtypeStructs of a (K, N) ``BitmapWeight`` in (128, 128) tiles
    at ``sparsity`` (value slots: the mean tile count plus 8 %)."""
    bk = bn = 128
    q = -(-int(bk * bn * (1 - sparsity) * 1.08) // bn)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    return BitmapWeight(
        packed_bits=s(lead + (k // bk, n // bn, bk // WORD_ROWS, bn),
                      jnp.int32),
        values=s(lead + (k // bk, n // bn, q, bn), dtype),
        shape=(k, n), block=(bk, bn))


def _assert_kernel(compiled):
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK], ids=["decode",
                                                           "prefill"])
@pytest.mark.parametrize("name", sorted(OLMO))
def test_bitmap_spmm_compiles_at_olmo_widths(one_chip, name, m):
    k, n = OLMO[name]
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, w: bitmap_spmm(x, w)).lower(
        x, _weight(k, n, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("name", ["w_down"])
def test_bitmap_spmm_compiles_at_cell_decode_rows(one_chip, name):
    """K = 8192 in one strip of 64 tiles (four unrolled loop iterations)
    at the benchmark cell's 16 decode rows, with bf16 values."""
    k, n = OLMO[name]
    x = jax.ShapeDtypeStruct((CELL_SLOTS, k), jnp.bfloat16,
                             sharding=one_chip)
    w = _weight(k, n, one_chip, dtype=jnp.bfloat16)
    _assert_kernel(jax.jit(lambda x, w: bitmap_spmm(x, w)).lower(
        x, w).compile())


@pytest.mark.parametrize("name", ["wq", "w_up", "w_down"])
def test_bitmap_spmm_compiles_at_cell_prefill_rows(one_chip, name):
    """A prefill call's 16 x 32 rows in one row block, each expanded tile
    carried through four 128-row sub-blocks; K in one strip, or for
    ``w_down`` (K = 8192) in four strips of 16 tiles."""
    k, n = OLMO[name]
    x = jax.ShapeDtypeStruct((CELL_SLOTS * CHUNK, k), jnp.bfloat16,
                             sharding=one_chip)
    w = _weight(k, n, one_chip, dtype=jnp.bfloat16)
    _assert_kernel(jax.jit(lambda x, w: bitmap_spmm(x, w)).lower(
        x, w).compile())


@pytest.mark.parametrize("values_dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_compiles_for_each_value_dtype(one_chip, values_dtype):
    x = jax.ShapeDtypeStruct((SLOTS, 2048), jnp.bfloat16, sharding=one_chip)
    w = _weight(2048, 8192, one_chip, dtype=values_dtype)
    _assert_kernel(jax.jit(lambda x, w: bitmap_spmm(x, w)).lower(
        x, w).compile())


# capacity rows per expert at top-8 of 40 (capacity factor 1.25): decode
# over 4 slots, and a 4 x 32-token prefill chunk; the benchmark cell's
# prefill call, its chunk folded into the batch: 16 x 32 rows per expert
@pytest.mark.parametrize("m", [2, 33, CELL_SLOTS * CHUNK],
                         ids=["decode", "prefill", "cell-prefill"])
@pytest.mark.parametrize("k,n", [(GRANITE_D, GRANITE_FF),
                                 (GRANITE_FF, GRANITE_D)],
                         ids=["w_up", "w_down"])
def test_grouped_kernel_compiles_at_granite_expert_widths(one_chip, k, n,
                                                          m):
    x = jax.ShapeDtypeStruct((GRANITE_EXPERTS, m, k), jnp.bfloat16,
                             sharding=one_chip)
    w = _weight(k, n, one_chip, lead=(GRANITE_EXPERTS,))
    compiled = jax.jit(lambda x, w: bitmap_spmm_grouped(x, w)).lower(
        x, w).compile()
    _assert_kernel(compiled)


def test_granite_decode_step_compiles_at_published_depth(one_chip):
    """The whole serving decode step of granite-3.0-3b-a800m (32 layers,
    GQA 24/8, 40 experts top-8, dense router and head) over the benchmark
    cell's 16 slots and paged KV cache, every attention projection and
    expert stack packed: the grouped expert kernels, the MoE dispatch and
    the step around them compile for the chip."""
    from repro.configs import get_config
    from repro.launch.steps import build_serve_step
    from repro.models.model import (MOE_SCOPES, cache_structs,
                                    paged_layout, param_structs)
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m"),
                              param_dtype="bfloat16")
    max_len, page_len = 1024, 16
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    p, d, e = cfg.num_periods, cfg.d_model, cfg.num_experts
    hq = cfg.num_heads * cfg.resolved_head_dim
    hkv = cfg.num_kv_heads * cfg.resolved_head_dim
    w = lambda k, n, lead: _weight(k, n, one_chip, lead=lead,
                                   dtype=jnp.bfloat16)
    packed = {"b0": {
        "attn": {"norm": None, "wq": w(d, hq, (p,)), "wk": w(d, hkv, (p,)),
                 "wv": w(d, hkv, (p,)), "wo": w(hq, d, (p,))},
        "moe": {"norm": None, "router": None,
                "w_gate": w(d, cfg.d_ff, (p, e)),
                "w_up": w(d, cfg.d_ff, (p, e)),
                "w_down": w(cfg.d_ff, d, (p, e))}}}
    tables = {b: jax.ShapeDtypeStruct((CELL_SLOTS, n), jnp.int32,
                                      sharding=one_chip)
              for b, n in paged_layout(cfg, max_len, page_len).items()}
    tok = jax.ShapeDtypeStruct((CELL_SLOTS, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((CELL_SLOTS,), jnp.int32, sharding=one_chip)
    step = build_serve_step(cfg, impl="pallas")
    compiled = jax.jit(
        lambda params, cache, tok, pos, packed, tables: step(
            params, cache, tok, pos, packed=packed, page_tables=tables)
    ).lower(place(param_structs(cfg)),
            place(cache_structs(cfg, CELL_SLOTS, max_len, page_len)),
            tok, pos, packed, tables).compile()
    hlo = compiled.as_text()
    # q, k, v, o and the three grouped expert products, once in the scan
    assert hlo.count('custom_call_target="tpu_custom_call"') == 7
    for scope in MOE_SCOPES:
        assert f"/{scope}/" in hlo, scope


def test_olmo_prefill_step_compiles_at_cell_shapes(one_chip):
    """The whole chunked-prefill step of olmo-1b (16 layers, MHA 16
    heads) at the olmo-1b.gen cell's shapes: 16 slots of a 32-token
    chunk over a paged KV cache (``max_len`` 1024, ``page_len`` 16), the
    blocked causal attention call and every projection packed: the
    prefill kernels and the attention core around them compile for the
    chip."""
    from repro.configs import get_config
    from repro.launch.steps import build_prefill_step
    from repro.models.model import (ATTENTION_SCOPE, cache_structs,
                                    paged_layout, param_structs)
    cfg = dataclasses.replace(get_config("olmo-1b"), param_dtype="bfloat16")
    max_len, page_len = 1024, 16
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    p, d, ff = cfg.num_periods, cfg.d_model, cfg.d_ff
    w = lambda k, n: _weight(k, n, one_chip, lead=(p,), dtype=jnp.bfloat16)
    packed = {"b0": {
        "attn": {"norm": None, "wq": w(d, d), "wk": w(d, d), "wv": w(d, d),
                 "wo": w(d, d)},
        "mlp": {"norm": None, "w_gate": w(d, ff), "w_up": w(d, ff),
                "w_down": w(ff, d)}}}
    tables = {b: jax.ShapeDtypeStruct((CELL_SLOTS, n), jnp.int32,
                                      sharding=one_chip)
              for b, n in paged_layout(cfg, max_len, page_len).items()}
    tok = jax.ShapeDtypeStruct((CELL_SLOTS, CHUNK), jnp.int32,
                               sharding=one_chip)
    vec = jax.ShapeDtypeStruct((CELL_SLOTS,), jnp.int32, sharding=one_chip)
    step = build_prefill_step(cfg, impl="pallas")
    compiled = jax.jit(
        lambda params, cache, tok, pos, lens, packed, tables: step(
            params, cache, tok, pos, lens, packed=packed,
            page_tables=tables)
    ).lower(place(param_structs(cfg)),
            place(cache_structs(cfg, CELL_SLOTS, max_len, page_len)),
            tok, vec, vec, packed, tables).compile()
    hlo = compiled.as_text()
    # q, k, v, o and the three MLP products, once in the period scan
    assert hlo.count('custom_call_target="tpu_custom_call"') == 7
    assert f"/{ATTENTION_SCOPE}/" in hlo
