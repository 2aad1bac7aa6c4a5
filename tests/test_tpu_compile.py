"""The serving path's Pallas kernels compile for a TPU v5e.

Compiled for a described ``v5e:2x2`` topology (no chip attached): the
chip's own compiler refuses a block that breaks its tiling rules or an
operation it cannot lower, which interpret mode never checks.  Shapes are
olmo-1b's published projections and LM head at the decode slot count and
the prefill chunk, and granite-moe-3b-a800m's expert stacks through the
grouped kernel.  Each test asserts the kernel is in the compiled program
as a ``tpu_custom_call``.
"""
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


@pytest.mark.parametrize("values_dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_compiles_for_each_value_dtype(one_chip, values_dtype):
    x = jax.ShapeDtypeStruct((SLOTS, 2048), jnp.bfloat16, sharding=one_chip)
    w = _weight(2048, 8192, one_chip, dtype=values_dtype)
    _assert_kernel(jax.jit(lambda x, w: bitmap_spmm(x, w)).lower(
        x, w).compile())


# capacity rows per expert at top-8 of 40 (capacity factor 1.25): decode
# over 4 slots, and a 4 x 32-token prefill chunk
@pytest.mark.parametrize("m", [2, 33], ids=["decode", "prefill"])
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
