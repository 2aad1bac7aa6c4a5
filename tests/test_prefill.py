"""Chunked batched prefill: token equivalence against the teacher-forcing
oracle (contiguous and paged, ragged prompt lengths), planner accounting,
typed budget rejection, TTFT decomposition, recorded fallbacks.

The contract with teacher-forcing: the cache writes are bit-identical,
attention outputs differ only by summation order (both pinned against
the old token-by-token walk in ``test_prefill_attention.py``), and the
served tokens match (pinned here)."""
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.serve import (PagedKVCache, PrefillPlanner, RequestRejected,
                         ServeEngine, poisson_trace)


def _ragged_trace(cfg, n=5, seed=3):
    """Prompt lengths 1..13 — deliberately not multiples of the chunk
    (and including single-token prompts, which skip prefill entirely)."""
    return poisson_trace(n, rate=0.7, seed=seed, vocab_size=cfg.vocab_size,
                         prompt_len=(1, 13), max_new=(3, 8))


def _run_tokens(cfg, trace, *, sparsity=0.0, **engine_kw):
    eng = ServeEngine(cfg, num_slots=2, max_len=32, sparsity=sparsity,
                      seed=0, **engine_kw)
    reqs = [eng.submit(**spec) for spec in trace]
    eng.run()
    return [r.tokens for r in reqs], eng


# ------------------------------------------------------- equivalence -------


@pytest.mark.parametrize("arch", ["olmo-1b", "gemma3-4b",
                                  "granite-moe-3b-a800m"])
@pytest.mark.parametrize("sparsity", [0.0, 0.75])
def test_prefill_matches_teacher_forcing(arch, sparsity):
    """Chunked prefill is token-identical to the legacy teacher-forced
    prompt walk on identical ragged traces — full attention, sliding
    windows and MoE, pruned or not, contiguous *and* paged.  The blocked
    attention call writes a bit-identical cache and attends each token
    to the (position, K/V) pairs decode would, so only summation order
    differs, and the served tokens are identical."""
    cfg = get_smoke_config(arch)
    trace = _ragged_trace(cfg)
    base, _ = _run_tokens(cfg, trace, sparsity=sparsity)
    pf, eng = _run_tokens(cfg, trace, sparsity=sparsity, prefill_chunk=4)
    paged, _ = _run_tokens(cfg, trace, sparsity=sparsity, prefill_chunk=4,
                           paged=True, page_len=8)
    assert pf == base
    assert paged == base
    assert all(toks for toks in pf)
    rep = eng.report()["prefill"]
    assert rep["enabled"] and rep["calls"] > 0
    assert rep["tokens_prefilled"] == sum(
        len(t["prompt"]) - 1 for t in trace)


def test_chunk_wider_than_window_and_page():
    """A chunk that wraps a sliding-window ring *within one call* (and
    spans page boundaries) still matches the oracle: only the latest
    chunk token per ring line is written, as decode's order leaves it."""
    cfg = get_smoke_config("gemma3-4b")        # window=8 local blocks
    trace = poisson_trace(4, rate=0.5, seed=5, vocab_size=cfg.vocab_size,
                          prompt_len=(18, 28), max_new=(3, 6))
    base, _ = _run_tokens(cfg, trace)
    wide, _ = _run_tokens(cfg, trace, prefill_chunk=16)
    wide_paged, _ = _run_tokens(cfg, trace, prefill_chunk=16, paged=True,
                                page_len=8)
    ragged_paged, _ = _run_tokens(cfg, trace, prefill_chunk=5, paged=True,
                                  page_len=8)
    assert wide == base and wide_paged == base and ragged_paged == base


def test_prefill_uses_fewer_engine_steps():
    """The point of the subsystem: a long prompt costs ceil((L-1)/C)
    chunk calls instead of L-1 full-batch decode steps."""
    cfg = get_smoke_config("olmo-1b")
    trace = [{"prompt": list(range(1, 26)), "max_new_tokens": 3,
              "arrival": 0.0}]
    base, beng = _run_tokens(cfg, trace)
    pf, peng = _run_tokens(cfg, trace, prefill_chunk=8)
    assert pf == base
    assert beng.report()["steps"] == 24 + 3        # 24 prompt walk + gen
    prep = peng.report()
    assert prep["prefill"]["calls"] == 3           # ceil(24 / 8)
    assert prep["steps"] < beng.report()["steps"]
    # decode ran only for real generation (plus admission-idle steps)
    assert prep["prefill"]["decode_steps"] < beng.report()["steps"]


# ------------------------------------------------------------ planner ------


def test_planner_chunks_ragged_prompts():
    p = PrefillPlanner(num_slots=3, chunk=4)
    assert not p.start(0, [7])                 # single token: no prefill
    assert p.start(1, list(range(10)))         # 9 positions -> 4+4+1
    assert p.start(2, list(range(6)))          # 5 positions -> 4+1
    tokens, pos, lens, done = p.next_call()
    assert tokens.shape == (3, 4)
    assert lens.tolist() == [0, 4, 4] and pos.tolist() == [0, 0, 0]
    assert done == []
    tokens, pos, lens, done = p.next_call()
    assert lens.tolist() == [0, 4, 1] and pos.tolist() == [0, 4, 4]
    assert done == [2] and p.in_prefill(1) and not p.in_prefill(2)
    tokens, pos, lens, done = p.next_call()
    assert lens.tolist() == [0, 1, 0] and done == [1]
    assert not p.has_work
    assert p.calls == 3 and p.tokens_prefilled == 9 + 5
    # a mid-prefill slot always parks on its next unwritten position
    p.start(0, list(range(7)))
    p.next_call()
    assert p.next_pos(0) == 4


def test_planner_batches_multiple_requests_per_call():
    p = PrefillPlanner(num_slots=4, chunk=8)
    for slot in range(4):
        assert p.start(slot, list(range(9)))
    _, _, lens, done = p.next_call()
    assert lens.tolist() == [8, 8, 8, 8]       # all four in one call
    assert done == [0, 1, 2, 3]
    assert p.report()["lane_utilization"] == 1.0


# ------------------------------------------------ admission / rejection ----


def test_nonpositive_budget_rejected_typed():
    """max_new_tokens < 1 used to quietly generate one token anyway (the
    budget check runs only after appending); now it is a typed reject
    and the engine keeps serving."""
    cfg = get_smoke_config("olmo-1b")
    eng = ServeEngine(cfg, num_slots=2, max_len=32, seed=0)
    with pytest.raises(RequestRejected):
        eng.submit([1, 2], max_new_tokens=0)
    with pytest.raises(RequestRejected):
        eng.submit([1, 2], max_new_tokens=-3)
    req = eng.submit([1, 2], max_new_tokens=1)
    eng.run()
    assert len(req.tokens) == 1


def test_recurrent_arch_falls_back_with_reason():
    cfg = get_smoke_config("rwkv6-3b")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng = ServeEngine(cfg, num_slots=2, max_len=16, seed=0,
                          prefill_chunk=8)
    assert eng.prefill_chunk == 0
    assert "recurrent" in eng.prefill_fallback
    assert any("teacher-forcing" in str(w.message) for w in caught)
    req = eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    assert len(req.tokens) == 3
    rep = eng.report()["prefill"]
    assert rep["enabled"] is False and rep["fallback"]


# -------------------------------------------------------------- timing -----


@pytest.mark.parametrize("prefill_chunk", [0, 4])
def test_ttft_decomposes_into_components(prefill_chunk):
    """first_token_s = queue + prefill + first-decode for every done
    request, in both the chunked and the legacy teacher-forcing mode —
    prompt-walk time is no longer conflated with queueing."""
    cfg = get_smoke_config("olmo-1b")
    trace = _ragged_trace(cfg, n=4)
    eng = ServeEngine(cfg, num_slots=2, max_len=32, seed=0,
                      prefill_chunk=prefill_chunk)
    reqs = [eng.submit(**spec) for spec in trace]
    rep = eng.run()
    for r in reqs:
        for part in (r.queue_s, r.prefill_s, r.first_decode_s):
            assert part is not None and part >= 0
        assert r.queue_s + r.prefill_s + r.first_decode_s == pytest.approx(
            r.first_token_s, abs=1e-9)
    for key in ("queue_s", "prefill_s", "first_decode_s"):
        assert np.isfinite(rep["ttft"][key]["p50"])


# ----------------------------------------------------------- paging --------


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 40), st.integers(1, 24)),
                min_size=1, max_size=6),
       st.sampled_from([4, 8]))
def test_ensure_range_equals_stepwise_ensure(ranges, page_len):
    """Bulk-mapping a chunk's pages is observationally identical to the
    per-position ensure walk the decode path does (same tables, same
    allocation counts) — for full-attention and ring pools alike."""
    cfg = get_smoke_config("gemma3-4b")
    bulk = PagedKVCache(cfg, num_slots=2, max_len=32, page_len=page_len)
    step = PagedKVCache(cfg, num_slots=2, max_len=32, page_len=page_len)
    for kv in (bulk, step):
        kv.reserve(32)
        kv.admit(0, 32)
    for start, n in ranges:
        bulk.ensure_range(0, start, start + n)
        for pos in range(start, start + n):
            step.ensure(0, pos)
        for b in bulk.pools:
            assert np.array_equal(bulk.pools[b].table, step.pools[b].table)
            assert bulk.pools[b].in_use == step.pools[b].in_use
            assert sorted(bulk.pools[b].free) == sorted(step.pools[b].free)
