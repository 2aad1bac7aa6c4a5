"""Chunked-prefill attention core: the blocked causal call against the
token-by-token walk it replaced.

``_walk`` below is that walk, kept here as the oracle: for each chunk
token in turn it writes the token's K/V line, gathers the slot's whole
cache view and runs ``decode_attention`` at the token's position.  The
blocked ``_prefill_attn_core`` must leave the cache bit-identical (every
live line; page 0, the trash page, holds unspecified padding writes in
both) and attend each valid token to the same (position, K/V) pairs, so
its outputs differ only by summation order: at f32 on the CPU they agree
within ``ATOL`` / ``RTOL``.  Padding lanes' outputs are discarded by the
engine and not compared.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import layers as L
from repro.models.model import (_prefill_attn_core, attn_capacity,
                                latest_writes, paged_addressing)

ATOL = RTOL = 1e-5
B, MAX_LEN = 3, 32


def _walk(q, k, v, cache, cfg, blk, pos, lens, page_table):
    """The per-token write-then-attend scan the blocked call replaced."""
    c_chunk = q.shape[1]
    posv = jnp.asarray(pos)
    posb = posv[:, None] + jnp.arange(c_chunk)[None, :]
    q = L.rope(q, posb, cfg.rope_theta)
    k = L.rope(k, posb, cfg.rope_theta)
    if page_table is not None:
        plen = cache["k"].shape[1]
        cap, ring = paged_addressing(page_table.shape[1], plen, blk.window)
    else:
        cap = cache["k"].shape[1]
        ring = blk.window is not None and cap == blk.window

    def tok_step(carry, xs):
        k_cache, v_cache = carry
        q_t, k_t, v_t, t = xs
        pos_t = posv + t
        valid = t < lens
        slot = (pos_t % cap) if ring else jnp.clip(pos_t, 0, cap - 1)
        if page_table is not None:
            k_cache, v_cache = L.paged_kv_update(
                k_cache, v_cache, k_t[:, None], v_t[:, None], page_table,
                slot, valid=valid)
            k_att = L.paged_gather(k_cache, page_table)
            v_att = L.paged_gather(v_cache, page_table)
        else:
            k_cache, v_cache = L.slot_kv_update(
                k_cache, v_cache, k_t[:, None], v_t[:, None], slot,
                valid=valid)
            k_att, v_att = k_cache, v_cache
        o = L.decode_attention(q_t[:, None], k_att, v_att, pos_t,
                               window=blk.window, ring=ring)
        return (k_cache, v_cache), o[:, 0]

    (k_cache, v_cache), outs = jax.lax.scan(
        tok_step, (cache["k"], cache["v"]),
        (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
         jnp.arange(c_chunk)))
    return outs.swapaxes(0, 1), {"k": k_cache, "v": v_cache}


def _setup(arch, paged, page_len, seed=0):
    """An f32 smoke config's windowed (if any) attention block, a cache
    full of random lines (stale contents the masks must exclude) and,
    when ``paged``, page tables over a shuffled pool."""
    cfg = dataclasses.replace(get_smoke_config(arch),
                              compute_dtype="float32")
    blk = max((b for b in cfg.pattern if b.mixer == "attn"),
              key=lambda b: b.window is not None)
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    cap = attn_capacity(blk, MAX_LEN)
    table = None
    if paged:
        slots = -(-cap // page_len)
        pages = 1 + rng.permutation(B * slots)
        table = jnp.asarray(pages.reshape(B, slots), jnp.int32)
        shape = (B * slots + 1, page_len, hkv, hd)
    else:
        shape = (B, cap, hkv, hd)
    cache = {n: jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for n in ("k", "v")}
    return cfg, blk, cache, table


def _chunk(cfg, c, seed):
    rng = np.random.default_rng(seed)
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return [jnp.asarray(rng.standard_normal((B, c, n, hd)), jnp.float32)
            for n in (h, hkv, hkv)]


# arch, paged, page_len, chunk, (pos, lens) of each call in turn: MHA and
# GQA full attention, gemma3's window-8 ring narrower than the chunk,
# padding lanes (lens < C, lens = 0) and a second chunk at pos > 0
CASES = {
    "mha-contiguous": ("olmo-1b", False, 0, 8,
                       [([0, 0, 0], [8, 5, 0]), ([8, 5, 0], [8, 8, 3])]),
    "mha-paged": ("olmo-1b", True, 8, 8,
                  [([0, 3, 0], [8, 5, 0]), ([8, 8, 0], [6, 8, 8])]),
    "gqa-contiguous": ("granite-moe-3b-a800m", False, 0, 8,
                       [([0, 0, 0], [8, 1, 8]), ([8, 1, 8], [4, 0, 8])]),
    "gqa-paged": ("granite-moe-3b-a800m", True, 4, 8,
                  [([0, 0, 2], [8, 7, 0]), ([8, 7, 2], [8, 8, 5])]),
    "ring-contiguous": ("gemma3-4b", False, 0, 16,
                        [([0, 0, 0], [16, 11, 0]), ([16, 11, 0],
                                                    [16, 9, 13])]),
    "ring-paged": ("gemma3-4b", True, 4, 16,
                   [([0, 0, 0], [16, 11, 0]), ([16, 11, 0], [16, 9, 13])]),
    "ring-paged-wide-page": ("gemma3-4b", True, 16, 16,
                             [([0, 5, 0], [16, 3, 12]), ([16, 8, 12],
                                                         [10, 16, 0])]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_blocked_call_matches_token_walk(case):
    arch, paged, page_len, c, calls = CASES[case]
    cfg, blk, cache, table = _setup(arch, paged, page_len)
    walk = jax.jit(lambda q, k, v, cache, pos, lens: _walk(
        q, k, v, cache, cfg, blk, pos, lens, table))
    blocked = jax.jit(lambda q, k, v, cache, pos, lens: _prefill_attn_core(
        q, k, v, cache, cfg, blk, pos, lens, table))
    want_cache = got_cache = cache
    for i, (pos, lens) in enumerate(calls):
        q, k, v = _chunk(cfg, c, seed=10 + i)
        pos, lens = jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32)
        want_o, want_cache = walk(q, k, v, want_cache, pos, lens)
        got_o, got_cache = blocked(q, k, v, got_cache, pos, lens)
        for n in ("k", "v"):
            want, got = np.asarray(want_cache[n]), np.asarray(got_cache[n])
            if paged:                      # page 0 is the trash page
                want, got = want[1:], got[1:]
            np.testing.assert_array_equal(got, want, err_msg=f"{n} call {i}")
        live = np.arange(c)[None, :] < np.asarray(lens)[:, None]
        np.testing.assert_allclose(np.asarray(got_o)[live],
                                   np.asarray(want_o)[live],
                                   atol=ATOL, rtol=RTOL,
                                   err_msg=f"output call {i}")


def _primitives(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("c", [4, 32])
def test_blocked_call_gathers_each_pool_once(c):
    """No scan over the chunk, and one gather of each paged pool per
    call whatever the chunk length."""
    cfg, blk, cache, table = _setup("granite-moe-3b-a800m", True, 8)
    q, k, v = _chunk(cfg, c, seed=0)
    pos = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), c, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, cache: _prefill_attn_core(
        q, k, v, cache, cfg, blk, pos, lens, table))(q, k, v, cache).jaxpr
    eqns = list(_primitives(jaxpr))
    assert not [e for e in eqns if e.primitive.name == "scan"]
    pool_gathers = [e for e in eqns if e.primitive.name == "gather"
                    and e.invars[0].aval.shape == cache["k"].shape]
    assert len(pool_gathers) == 2


def test_each_cache_line_written_once():
    """A chunk of 16 over a ring of 8 lines: only the latest valid token
    per line is written, and padding lanes write nothing live."""
    t = np.arange(16)
    slot = jnp.asarray(np.stack([t % 8, (t + 3) % 8, t % 8]), jnp.int32)
    lens = np.array([16, 13, 5])
    valid = jnp.asarray(t[None] < lens[:, None])
    live = np.asarray(latest_writes(slot, valid))
    np.testing.assert_array_equal(live[0], t >= 8)
    np.testing.assert_array_equal(live[1], (t >= 5) & (t < 13))
    np.testing.assert_array_equal(live[2], t < 5)
    for row, s in zip(live, np.asarray(slot)):
        assert len(set(s[row])) == row.sum()
