"""Whole-stack bitmap weight streaming: packed-vs-dense equivalence,
manifest/fallback surfacing, the per-step weight stream, sampling."""
import math
import warnings

import numpy as np
import pytest

from repro.configs import ARCHS, get_smoke_config
from repro.models.config import BlockCfg, ModelConfig
from repro.serve import ServeEngine, pack_model, poisson_trace
from repro.serve.packed import ROUTED_EXPERT, activated_scale


def _run_tokens(cfg, *, stream, sparsity=0.0, seed=0, n_requests=4,
                head_sparsity=None, **engine_kw):
    eng = ServeEngine(cfg, num_slots=2, max_len=32, sparsity=sparsity,
                      seed=seed, stream_weights=stream,
                      bitmap_head=stream, head_sparsity=head_sparsity,
                      **engine_kw)
    trace = poisson_trace(n_requests, rate=0.7, seed=3,
                          vocab_size=cfg.vocab_size, max_new=(4, 8))
    reqs = [eng.submit(**spec) for spec in trace]
    eng.run()
    return [r.tokens for r in reqs], eng


# ------------------------------------------------------- equivalence -------


@pytest.mark.parametrize("arch", ARCHS)
def test_packed_streaming_matches_dense_tokens(arch):
    """sparsity=0: the fully-packed engine reproduces the dense engine's
    tokens exactly on every registered arch (sliding window, GQA, MoE,
    hybrid mamba/MoE, rwkv, the frames and patch frontends) — packing is
    lossless, and the bitmap dispatch, which decodes the packed arrays
    inside every served step, is numerically identical to dense ``@``."""
    cfg = get_smoke_config(arch)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # e.g. granite's dense head
        packed_toks, eng = _run_tokens(cfg, stream=True)
        dense_toks, _ = _run_tokens(cfg, stream=False)
    assert packed_toks == dense_toks
    assert all(toks for toks in packed_toks)
    assert eng.packed is not None and eng.packed.packed_entries


def test_packed_streaming_lossless_under_pruning():
    """At 75% sparsity the packed stream still equals dense dispatch of
    the *pruned* weights token-for-token (the budget keeps every
    surviving non-zero)."""
    cfg = get_smoke_config("olmo-1b")
    packed_toks, eng = _run_tokens(cfg, stream=True, sparsity=0.75)
    dense_toks, _ = _run_tokens(cfg, stream=False, sparsity=0.75)
    # dense engine serves a dense head; packed head is per-tensor pruned
    # to head_sparsity — neutralise by comparing hidden-stack effects
    # only via a 0-head-sparsity packed engine
    packed0, _ = _run_tokens(cfg, stream=True, sparsity=0.75,
                             head_sparsity=0.0)
    assert packed0 == dense_toks
    assert eng.report()["weight_stream"]["reduction"] > 2.0


# ------------------------------------------- manifest / weight stream ------


def test_pack_model_manifest_records_fallbacks():
    cfg = get_smoke_config("granite-moe-3b-a800m")
    eng = ServeEngine(cfg, num_slots=2, max_len=16, sparsity=0.5, seed=0)
    pm = eng.packed
    packed_paths = {e.path for e in pm.packed_entries}
    assert any("attn/wq" in p for p in packed_paths)
    # MoE expert stacks ride the grouped bitmap layout since PR 5
    assert any("moe/w_gate" in p for p in packed_paths)
    fb = {e.path: e.reason for e in pm.fallback_entries}
    assert all(r for r in fb.values())
    ws = eng.report()["weight_stream"]
    assert ws["sparse_bytes_per_step"] < ws["dense_bytes_per_step"]
    assert ws["packed_tensors"] == len(pm.packed_entries)
    assert ws["fallbacks"] == {e.path: e.reason for e in pm.fallback_entries}


def test_stacked_pack_roundtrip():
    """Period-stacked packing is lossless per period, shares one budget,
    and the stacked unpack oracle reproduces the input exactly."""
    from repro.sparse.format import (pack_bitmap_stacked,
                                     unpack_bitmap_stacked)
    r = np.random.default_rng(2)
    w = r.standard_normal((3, 64, 128)).astype(np.float32)
    w *= r.random((3, 64, 128)) >= 0.6
    bw = pack_bitmap_stacked(w, block=(64, 64))
    assert bw.packed_bits.shape[0] == 3
    np.testing.assert_array_equal(np.asarray(unpack_bitmap_stacked(bw)), w)


def test_expert_pack_roundtrip_properties():
    """Property tests for the (P, E, K, N) expert layout: packing is
    lossless for any stack shape/sparsity, the value-slot budget is
    shared (= max tile non-zero count across the whole stack), and the
    grouped dispatch equals the dense per-expert einsum on both the xla
    ref and the interpreted Pallas kernel."""
    from hypothesis import given, settings
    from hypothesis import strategies as st
    from repro.kernels import ops
    from repro.sparse.format import (BitmapWeight, pack_bitmap_experts,
                                     unpack_bitmap_experts)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 2), st.integers(1, 4),
           st.sampled_from([16, 32, 64]), st.sampled_from([8, 16, 32]),
           st.floats(0.0, 0.95), st.integers(0, 2 ** 31 - 1))
    def check(p, e, k, n, sparsity, seed):
        r = np.random.default_rng(seed)
        w = r.standard_normal((p, e, k, n)).astype(np.float32)
        w *= r.random((p, e, k, n)) >= sparsity
        bw = pack_bitmap_experts(w, block=(k, n))
        assert bw.packed_bits.shape[:2] == (p, e)
        np.testing.assert_array_equal(np.asarray(unpack_bitmap_experts(bw)),
                                      w)
        tile_nnz = (w != 0).reshape(p * e, -1).sum(-1)
        # the budget is the max tile non-zero count in whole BN-wide rows
        assert bw.budget == -(-max(1, int(tile_nnz.max())) // n) * n
        # grouped dispatch == per-expert dense matmul (one period slice)
        per = BitmapWeight(packed_bits=bw.packed_bits[0],
                           values=bw.values[0],
                           shape=bw.shape, block=bw.block)
        x = r.standard_normal((e, 3, k)).astype(np.float32)
        want = np.einsum("gmk,gkn->gmn", x, w[0])
        got = ops.bitmap_spmm_grouped(x, per, impl="xla")
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)

    check()

    # the interpreted Pallas kernel agrees on one representative stack
    r = np.random.default_rng(7)
    w = r.standard_normal((3, 64, 32)).astype(np.float32)
    w *= r.random((3, 64, 32)) >= 0.7
    bw = pack_bitmap_experts(w[None], block=(64, 32))
    per = BitmapWeight(packed_bits=bw.packed_bits[0], values=bw.values[0],
                       shape=bw.shape,
                       block=bw.block)
    x = r.standard_normal((3, 4, 64)).astype(np.float32)
    got = ops.bitmap_spmm_grouped(x, per, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("gmk,gkn->gmn", x, w),
                               rtol=1e-4, atol=1e-4)


def test_head_fallback_is_surfaced():
    """A head that no (BK, BN) tile divides must warn and report the
    fallback instead of silently claiming head_compression=1.0."""
    cfg = ModelConfig(name="oddvocab", d_model=32, num_layers=2,
                      num_heads=2, num_kv_heads=2, d_ff=64,
                      vocab_size=251,  # prime: no BN % 8 divisor
                      pattern=(BlockCfg(mixer="attn"),),
                      tie_embeddings=True, max_seq_len=32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng = ServeEngine(cfg, num_slots=2, max_len=16, seed=0,
                          stream_weights=False)
    assert eng.lm_weight is None
    assert eng.head_fallback and "vocab=251" in eng.head_fallback
    assert any("dense" in str(w.message) for w in caught)
    rep = eng.report()
    assert rep["head_fallback"] == eng.head_fallback
    assert rep["head_compression"] == 1.0


# -------------------------------------------- full-stack coverage (PR 5) ---
#
# MoE expert stacks and SSM mixer projections ride the compressed bitmap
# path: token equivalence across the 5-arch × 2-sparsity × {decode,
# chunked prefill} × {contiguous, paged} matrix, a manifest snapshot
# locking per-arch fallbacks, expert-layout roundtrip properties, and
# the per-activated-expert traffic accounting rule.


def _mamba_smoke_cfg():
    """Pure-mamba decode cell (no registry arch is mamba-only; jamba
    interleaves).  d_state=6 makes x_proj's column count (dtr + 2N = 16)
    tileable, so all four mamba GEMMs pack."""
    return ModelConfig(
        name="mamba-smoke", d_model=64, num_layers=2, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=256,
        pattern=(BlockCfg(mixer="mamba", ffn="mlp"),),
        mamba_d_state=6, mamba_expand=2, mamba_conv=4,
        norm="rmsnorm", act="silu", tie_embeddings=False, max_seq_len=64)


MATRIX_ARCHS = ["granite-moe-3b-a800m", "moonshot-v1-16b-a3b",
                "jamba-v0.1-52b", "mamba", "rwkv6-3b"]


def _matrix_cfg(arch):
    return _mamba_smoke_cfg() if arch == "mamba" else get_smoke_config(arch)


_ORACLE_TOKENS = {}     # (cfg.name, sparsity) -> dense decode tokens


def _oracle_tokens(cfg, sparsity):
    key = (cfg.name, sparsity)
    if key not in _ORACLE_TOKENS:
        _ORACLE_TOKENS[key] = _run_tokens(cfg, stream=False,
                                          sparsity=sparsity,
                                          n_requests=3)[0]
    return _ORACLE_TOKENS[key]


@pytest.mark.parametrize("paged", [False, True], ids=["contig", "paged"])
@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("arch", MATRIX_ARCHS)
def test_full_stack_packed_matrix(arch, mode, paged):
    """The fully-packed engine — MoE expert stacks, SSM mixers, router
    and channel-mix included — reproduces the dense contiguous decode
    oracle token-for-token at sparsity 0 and 0.75, under chunked prefill
    and paging.  Archs whose engine records a fallback for a mode
    (recurrent mixers under prefill, attention-free archs under paging)
    still serve token-identically through the fallback."""
    cfg = _matrix_cfg(arch)
    kw = {}
    if mode == "prefill":
        kw["prefill_chunk"] = 2
    if paged:
        kw.update(paged=True, page_len=8)
    for sparsity in (0.0, 0.75):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            toks, eng = _run_tokens(cfg, stream=True, sparsity=sparsity,
                                    head_sparsity=0.0, n_requests=3, **kw)
        assert toks == _oracle_tokens(cfg, sparsity), (arch, mode, paged,
                                                       sparsity)
        assert all(t for t in toks)
        # the retired blanket reason must never reappear
        assert not any(e.reason == "no compressed dispatch path"
                       for e in eng.packed.manifest)


# Per-arch fallback snapshot: the exact (component, tensor) classes that
# may serve dense.  Everything is either a non-GEMM tensor (norms, conv,
# elementwise SSM state maps) or a smoke shape no (BK, BN) tile divides
# (granite's 5-way router, jamba's 16-wide x_proj columns... which is
# 12 here).  A regression that silently drops a GEMM class to dense —
# or grows a new fallback — fails this snapshot.
EXPECTED_FALLBACKS = {
    "granite-moe-3b-a800m": {("attn", "norm"), ("moe", "norm"),
                             ("moe", "router")},         # router (64, 5)
    "moonshot-v1-16b-a3b": {("attn", "norm"), ("moe", "norm")},
    "jamba-v0.1-52b": {("attn", "norm"), ("mlp", "norm"), ("moe", "norm"),
                       ("moe", "router"),                # router (64, 4)
                       ("mamba", "norm"), ("mamba", "conv_w"),
                       ("mamba", "conv_b"), ("mamba", "dt_bias"),
                       ("mamba", "A_log"), ("mamba", "D"),
                       ("mamba", "x_proj")},             # x_proj (128, 12)
    "mamba": {("mlp", "norm"), ("mamba", "norm"), ("mamba", "conv_w"),
              ("mamba", "conv_b"), ("mamba", "dt_bias"),
              ("mamba", "A_log"), ("mamba", "D")},
    "rwkv6-3b": {("rwkv", "norm"), ("rwkv", "mix_mu"), ("rwkv", "w0"),
                 ("rwkv", "u"), ("rwkv", "gn_scale"),
                 ("rwkv_cm", "norm"), ("rwkv_cm", "cm_mu")},
}


@pytest.mark.parametrize("arch", MATRIX_ARCHS)
def test_manifest_fallback_snapshot(arch):
    """Zero unexpected fallbacks per arch family, and every fallback is
    a non-GEMM tensor or an un-tileable smoke shape — never a GEMM class
    missing its dispatch path."""
    import jax
    from repro.models.model import init_params
    cfg = _matrix_cfg(arch)
    pm = pack_model(init_params(jax.random.PRNGKey(0), cfg))
    got = {tuple(e.path.split("/")[-2:]) for e in pm.fallback_entries}
    assert got == EXPECTED_FALLBACKS[arch], (arch, got)
    for e in pm.fallback_entries:
        assert ("not a GEMM operand" in e.reason
                or "no (BK, BN) tile" in e.reason), (e.path, e.reason)
    # expert stacks carry the grouped layout and their stored count;
    # rwkv's always-active mix_B is grouped but not router-gated
    for e in pm.packed_entries:
        comp, name = e.path.split("/")[-2:]
        if comp == "moe" and name in ("w_gate", "w_up", "w_down"):
            assert e.layout == "grouped" and e.experts == cfg.num_experts
        elif (comp, name) == ("rwkv", "mix_B"):
            assert e.layout == "grouped" and e.experts == 0
        else:
            assert e.layout == "stacked" and e.experts == 0


def test_expert_stream_accounting():
    """report()["weight_stream"] counts expert tensors once per
    *activated* expert per step (min(E, num_slots·top_k)), not once per
    stored expert — and matches a by-hand aggregation of the manifest."""
    cfg = get_smoke_config("granite-moe-3b-a800m")   # E=5, moe top_k=2
    eng = ServeEngine(cfg, num_slots=2, max_len=16, sparsity=0.5, seed=0,
                      head_sparsity=0.0)
    ws = eng.report()["weight_stream"]
    activated = min(cfg.num_experts, 2 * cfg.top_k)  # 4 of 5 experts
    assert ws["activated_experts"] == 2 * cfg.top_k

    def scaled(e, attr):
        b = getattr(e, attr)
        if e.experts:
            b = int(round(b * min(e.experts, ws["activated_experts"])
                          / e.experts))
        return b

    # granite's smoke vocab (255, deliberately non-divisible) makes the
    # head fall back to dense, so both sides carry the dense head term
    head_dense = cfg.d_model * cfg.vocab_size * 4
    head = (eng.lm_weight.hbm_bytes if eng.lm_weight is not None
            else head_dense)
    want_sparse = head + sum(scaled(e, "sparse_bytes")
                             for e in eng.packed.manifest)
    want_dense = head_dense + sum(scaled(e, "dense_bytes")
                                  for e in eng.packed.manifest)
    assert ws["sparse_bytes_per_step"] == want_sparse
    assert ws["dense_bytes_per_step"] == want_dense
    # the activation scaling actually bites: stored-stack totals are
    # strictly larger than the per-step activated accounting
    stored = head + sum(e.sparse_bytes for e in eng.packed.manifest)
    assert want_sparse < stored
    assert activated == 4


def _engine(arch="olmo-1b", num_slots=2, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # e.g. granite's dense head
        return ServeEngine(get_smoke_config(arch), num_slots=num_slots,
                           max_len=32, sparsity=0.5, seed=0, **kw)


@pytest.mark.parametrize("stream", [True, False], ids=["packed", "dense"])
@pytest.mark.parametrize("arch", ARCHS)
def test_weight_stream_sums_the_packed_leaves(arch, stream):
    """``weight_stream_report()`` (the benchmark's ``weight_mb_per_step``)
    equals a count taken from the engine's own leaves: each packed
    leaf's ``hbm_bytes``, each dense tensor's array bytes, router-gated
    expert stacks scaled to the experts a step activates, plus the LM
    head — packed or dense."""
    eng = _engine(arch, stream_weights=stream, bitmap_head=stream)
    cfg = eng.cfg
    activated = 2 * cfg.top_k if cfg.num_experts else None
    packed = eng.packed.blocks if eng.packed is not None else None
    sparse = dense = 0
    for bname, bdict in eng.params["blocks"].items():
        for comp, tensors in bdict.items():
            for name, w in tensors.items():
                routed = (w.shape[1] if (comp, name) in ROUTED_EXPERT
                          and w.ndim == 4 else 0)
                scale = activated_scale(routed, activated)
                d = int(round(w.size * w.dtype.itemsize * scale))
                bw = packed[bname][comp][name] if packed else None
                sparse += d if bw is None else int(round(bw.hbm_bytes
                                                         * scale))
                dense += d
    head_dense = cfg.d_model * cfg.vocab_size * 4
    sparse += (eng.lm_weight.hbm_bytes if eng.lm_weight is not None
               else head_dense)
    dense += head_dense
    ws = eng.weight_stream_report()
    assert ws["sparse_bytes_per_step"] == sparse
    assert ws["dense_bytes_per_step"] == dense
    assert sparse < dense if stream else sparse == dense


def test_weight_stream_tracks_quarantine():
    """Quarantining a packed tensor raises the per-step weight stream by
    exactly that tensor's dense minus packed bytes."""
    eng = _engine()
    before = eng.weight_stream_report()["sparse_bytes_per_step"]
    e = next(e for e in eng.packed.manifest if e.packed)
    rise = e.dense_bytes - e.sparse_bytes
    assert rise > 0
    eng.packed.quarantine(e.path, "test")
    after = eng.weight_stream_report()["sparse_bytes_per_step"]
    assert after == before + rise


@pytest.mark.parametrize("arch,knobs,steps", [
    # 2 periods x 7 matrices, each one (K, N) tile, and the head's two
    # 128-wide column blocks
    ("olmo-1b", {}, 2 * 7 + 2),
    # 2 periods x (4 attention matrices + 3 stacks of 5 experts), each
    # one tile at 2 slots x 1 capacity row; the head serves dense
    ("granite-moe-3b-a800m", {}, 2 * (4 + 3 * 5)),
    ("olmo-1b", {"stream_weights": False, "bitmap_head": False}, 0),
], ids=["olmo-1b", "granite-moe-3b-a800m", "dense"])
def test_weight_stream_counts_spmm_grid_steps(arch, knobs, steps):
    """``spmm_grid_steps_per_step`` equals the hand count of the
    ``bitmap_spmm`` grid steps one decode step runs."""
    ws = _engine(arch, **knobs).weight_stream_report()
    assert ws["spmm_grid_steps_per_step"] == steps


@pytest.mark.parametrize("arch,tiles", [
    # 2 periods x 7 matrices of one tile each; the head is not in prefill
    ("olmo-1b", 2 * 7),
    # 2 periods x (4 attention matrices + 3 stacks of 5 one-tile experts)
    ("granite-moe-3b-a800m", 2 * (4 + 3 * 5)),
])
def test_weight_stream_counts_prefill_expansions_per_tile(arch, tiles):
    """``spmm_prefill_expansions_per_tile`` equals the hand count of one
    prefill call at 16 slots x a 32-token chunk: 512 rows per matrix
    (and per expert's buffer, one capacity row per token), one row block
    each, so each of the call's packed tiles is expanded once."""
    eng = _engine(arch, num_slots=16, prefill_chunk=32)
    assert sum(math.prod(bw.values.shape[:-2])
               for _, bw in eng.packed.leaves()) == tiles
    # one row block: each tile expanded once (4.0 when a row block held
    # 128 rows)
    ws = eng.weight_stream_report()
    assert ws["spmm_prefill_expansions_per_tile"] == 1.0
    assert _engine(arch).weight_stream_report()[
        "spmm_prefill_expansions_per_tile"] is None   # no chunked prefill


# ---------------------------------------------------------- sampling -------


def test_sampling_reproducible_and_greedy_unchanged():
    cfg = get_smoke_config("olmo-1b")

    def run(top_k):
        eng = ServeEngine(cfg, num_slots=2, max_len=32, seed=0, top_k=top_k)
        g = eng.submit([5], max_new_tokens=6)
        s = eng.submit([5], max_new_tokens=6, temperature=1.0, seed=11)
        eng.run()
        return g.tokens, s.tokens

    g1, s1 = run(top_k=8)
    g2, s2 = run(top_k=8)
    assert g1 == g2 and s1 == s2          # per-request seeds: deterministic
    assert s1 != g1                       # temperature actually samples
    # greedy requests are untouched by the sampling machinery
    eng = ServeEngine(cfg, num_slots=2, max_len=32, seed=0)
    g3 = eng.submit([5], max_new_tokens=6)
    eng.run()
    assert g3.tokens == g1

    _, s_notrunc = run(top_k=0)
    assert len(s_notrunc) == 6            # top_k=0 path also samples


@pytest.mark.parametrize("d_model,d_ff,packs", [(64, 96, False),
                                                (128, 256, True)],
                         ids=["sub-lane", "lane-aligned"])
def test_chip_pack_falls_back_on_tiles_the_tpu_refuses(d_model, d_ff,
                                                       packs):
    """Packed for the compiled TPU kernel, a tensor whose tile breaks the
    chip's tiling rules serves dense with a typed manifest reason, and a
    lane-aligned one packs; the CPU pack of the same model packs both."""
    import jax
    from repro.models.model import init_params
    cfg = ModelConfig(name="lanes", d_model=d_model, num_layers=2,
                      num_heads=2, num_kv_heads=2, d_ff=d_ff,
                      vocab_size=256, pattern=(BlockCfg(mixer="attn"),),
                      max_seq_len=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    gemms = lambda pm: {e.path: e for e in pm.manifest
                        if "not a GEMM operand" not in e.reason}
    chip = gemms(pack_model(params, chip=True))
    assert chip and all(e.packed == packs for e in chip.values())
    for e in chip.values():
        assert packs or "does not fit the TPU" in e.reason, e.reason
    assert all(e.packed for e in gemms(pack_model(params)).values())
