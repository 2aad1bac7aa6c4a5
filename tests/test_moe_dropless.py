"""The served MoE path is dropless: every token reaches all of its top-k
experts, as in the published granite-3.0 models and in the benchmark's
plain reference (``bench/reference/moe.py``), even when every token of a
prefill chunk routes to the same experts.

Decode runs one token per slot (capacity 1 per expert, and a token's
top-k experts are distinct); chunked prefill folds the chunk into the
batch, so the same holds there.  One unfolded ``moe_ffn`` call over the
same chunk drops the rows past each expert's capacity, which is what the
served path must never do.  Also the engine's hand-counted
``expert_row_use_pct``.
"""
import dataclasses
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.config import BlockCfg, ModelConfig
from repro.serve import ServeEngine
import repro.serve.engine as engine_mod

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench.reference import model as ref_model  # noqa: E402
from bench.reference import moe as ref_moe  # noqa: E402
from bench.reference.common import Matmul  # noqa: E402

#: granite-shaped at smoke size: GQA (6 query heads over 2 KV heads),
#: RMSNorm, an MoE block in every layer, tied head; float32 throughout so
#: that the served logits and the reference's differ only in the order of
#: their sums
CFG = ModelConfig(
    name="granite-dropless", d_model=96, num_layers=2, num_heads=6,
    num_kv_heads=2, d_ff=32, vocab_size=128,
    pattern=(BlockCfg(mixer="attn", ffn="moe"),), num_experts=5, top_k=2,
    norm="rmsnorm", act="silu", rope_theta=10_000.0, tie_embeddings=True,
    max_seq_len=64, param_dtype="float32", compute_dtype="float32")
CHUNK = 8
SKEWED = (0, 1)                  # the experts every token routes to
#: served logits against the float32 reference, over the spread of the
#: reference's logits in the row: both sides are float32 and differ only
#: in summation order (paged chunked attention, per-expert products), by
#: at most 1.6e-5 of the spread here; prefill through one unfolded MoE
#: call per chunk, which drops the rows past each expert's capacity,
#: moves them by 6.4e-3
TOL = 1e-4


def _skewed_params(seed=0):
    """Random weights whose residual stream carries one large shared
    component (embedding column 0) that the router turns into the same
    top-2 experts for every token, in every layer."""
    from repro.models.model import init_params
    p = init_params(jax.random.PRNGKey(seed), CFG)
    p["embed"] = p["embed"].at[:, 0].set(0.5)
    router = p["blocks"]["b0"]["moe"]["router"]          # (P, D, E)
    for e, v in zip(SKEWED, (2.0, 1.5)):
        router = router.at[:, 0, e].set(v)
    p["blocks"]["b0"]["moe"]["router"] = router
    return p


def _served(monkeypatch, prompts, max_new):
    """Serve ``prompts`` through the packed engine with chunked prefill;
    returns the engine's pruned weights and, per request, its prompt, its
    tokens and the decode logits that chose each of them."""
    params = _skewed_params()
    monkeypatch.setattr(engine_mod, "init_params",
                        lambda _rng, _cfg: jax.tree.map(jnp.copy, params))
    eng = ServeEngine(CFG, num_slots=2, max_len=48, sparsity=0.5,
                      head_sparsity=0.0, seed=0, prefill_chunk=CHUNK,
                      paged=True, page_len=8)
    rows = {}
    decode = eng._decode

    def recording(tok, pos):
        live = {s: (r, len(r.tokens)) for s, r in eng.scheduler.active.items()
                if not eng.planner.in_prefill(s)}
        nxt, logits, cache = decode(tok, pos)
        for s, (r, j) in live.items():
            rows.setdefault(id(r), {})[j] = np.asarray(logits[s])
        return nxt, logits, cache
    eng._decode = recording
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    assert eng.report()["prefill"]["calls"] > 0
    out = [(p, list(r.tokens), [rows[id(r)][j] for j in range(len(r.tokens))])
           for p, r in zip(prompts, reqs)]
    return eng, out


def test_chunked_prefill_and_decode_match_the_reference(monkeypatch):
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, CFG.vocab_size, n)) for n in (21, 13)]
    eng, served = _served(monkeypatch, prompts, max_new=6)
    model = dataclasses.asdict(CFG)
    ref = jax.jit(lambda p, t: ref_model.logits(p, t, model))
    for prompt, toks, got in served:
        assert len(toks) == 6
        seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
        want = np.asarray(ref(eng.params, seq))[len(prompt) - 1:]
        got = np.stack(got)
        err = np.abs(got - want).max(-1) / want.std(-1)
        assert err.max() < TOL, err


def test_one_unfolded_call_over_the_chunk_drops_rows():
    """The chunk is skewed enough to overflow an expert: through one
    ``moe_ffn`` call of C tokens the rows past each expert's capacity are
    dropped (those tokens get no expert output), while the folded call the
    served path makes matches the dropless reference."""
    p = _skewed_params()
    moe = jax.tree.map(lambda a: a[0], p["blocks"]["b0"]["moe"])
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, CFG.vocab_size, CHUNK))
    x = p["embed"][toks]                                      # (C, D)
    xn = L.norm(x, moe["norm"], CFG.norm)
    probs = jax.nn.softmax(xn @ moe["router"], -1)
    assert (np.sort(np.asarray(jax.lax.top_k(probs, 2)[1]), -1)
            == np.asarray(SKEWED)).all()
    with jax.default_matmul_precision("highest"):
        want = ref_moe.ffn(moe, x, dataclasses.asdict(CFG), Matmul())
        folded = L.moe_ffn(moe, xn[:, None], CFG)[:, 0]
        unfolded = L.moe_ffn(moe, xn[None], CFG)[0]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(folded, want, atol=1e-4 * scale)
    cap = L.expert_capacity(CHUNK, CFG)
    assert cap < CHUNK
    # both skewed experts take the chunk's tokens in order: the first
    # ``cap`` are kept, the rest dropped from both
    np.testing.assert_allclose(unfolded[:cap], want[:cap], atol=1e-4 * scale)
    assert (np.abs(np.asarray(unfolded[cap:])) == 0).all()
    assert (np.abs(np.asarray(want[cap:])).max(-1) > 0.1 * scale).all()


def _out_shapes(jaxpr):
    for eqn in jaxpr.eqns:
        yield from (tuple(v.aval.shape) for v in eqn.outvars)
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _out_shapes(sub)


def test_moe_rows_sizes_the_dispatch_buffer():
    """``layers.moe_rows`` counts what ``moe_ffn`` builds: each batch
    row's capacity buffer, which the grouped expert products read, holds
    its ``computed`` rows (so ``expert_row_use_pct`` follows the dispatch)."""
    assert L.moe_rows(1, get_config("granite-moe-3b-a800m")) == (8, 40)
    routed, computed = L.moe_rows(CHUNK, CFG)
    assert (routed, computed) == (CHUNK * 2, 5 * L.expert_capacity(CHUNK,
                                                                   CFG))
    moe = jax.tree.map(lambda a: a[0], _skewed_params()["blocks"]["b0"]["moe"])
    x = jnp.zeros((3, CHUNK, CFG.d_model))
    jaxpr = jax.make_jaxpr(lambda x: L.moe_ffn(moe, x, CFG))(x).jaxpr
    assert (3, computed, CFG.d_model) in set(_out_shapes(jaxpr))


@pytest.mark.parametrize("cfg,slots,want", [
    (get_config("granite-moe-3b-a800m"), 16, 20.0),   # 16·8 of 40·16·1
    (CFG, 2, 40.0),                                   # 2·2 of 5·2·1
    (get_config("olmo-1b"), 16, None)])
def test_expert_row_use_pct_hand_count(cfg, slots, want):
    eng = types.SimpleNamespace(cfg=cfg, num_slots=slots)
    assert ServeEngine._expert_row_use_pct(eng) == want


def test_expert_row_use_pct_in_the_stream_report(monkeypatch):
    monkeypatch.setattr(engine_mod, "init_params",
                        lambda _rng, _cfg: _skewed_params())
    eng = ServeEngine(CFG, num_slots=2, max_len=16, sparsity=0.5)
    assert eng.weight_stream_report()["expert_row_use_pct"] == 40.0
    monkeypatch.undo()
    dense = ServeEngine(dataclasses.replace(
        CFG, pattern=(BlockCfg(mixer="attn", ffn="mlp"),), num_experts=0,
        top_k=0), num_slots=2, max_len=16, sparsity=0.5)
    assert "expert_row_use_pct" not in dense.weight_stream_report()
