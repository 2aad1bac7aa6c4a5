"""Deterministic fault injection + the step-level invariant auditor.

Three pillars:

* **audit is free of side effects** — with no faults injected,
  ``audit=True`` produces bit-identical tokens to the default engine
  across {contiguous, paged} × {reuse, preempt} × sparsity {0, 0.75}
  (the auditor reads, it never writes);
* **every fault recovers typed** — each injected fault kind (page-pool
  squeeze, forced preemption, prefix-eviction storm, NaN'd LM head,
  bit-flipped packed payload) terminates every request in a typed
  terminal state with *the clean run's exact tokens*, zero audit
  violations, and zero page leaks; corruption quarantines the offending
  tensor to its dense fallback with the reason in the manifest and
  ``report()["fallbacks"]``;
* **the contrast** — the same NaN fault with ``audit=False`` serves
  garbage (diverged tokens), which is exactly what the auditor exists
  to prevent.
"""
import warnings

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serve import FaultPlan, RequestState, ServeEngine

PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [4, 5, 6],
           [1, 2, 3, 4, 5, 6], [9, 8, 7, 6, 5]]


def _run(arch="olmo-1b", sparsity=0.5, max_new=6, **kw):
    cfg = get_smoke_config(arch)
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    eng = ServeEngine(cfg, seed=0, sparsity=sparsity, **kw)
    reqs = [eng.submit(p, max_new, arrival=float(i))
            for i, p in enumerate(PROMPTS)]
    rep = eng.run()
    return eng, rep, {r.rid: list(r.tokens) for r in reqs}


def _assert_clean(eng):
    """Zero page leaks after drain (paged engines)."""
    if eng.page_len:
        eng.kv.flush_prefix()
        eng.kv.audit()
        for pool in eng.kv.pools.values():
            assert not pool.ref and not pool.held


# ------------------------------------------- audit has no side effects ----


@pytest.mark.parametrize("sparsity", [0.0, 0.75])
@pytest.mark.parametrize("kw", [
    dict(),                                               # contiguous
    dict(paged=True, page_len=8),
    dict(paged=True, page_len=8, prefix_reuse=True),
    dict(paged=True, page_len=8, prefix_reuse=True,
         preempt=True, prefill_chunk=4),
], ids=["contig", "paged", "reuse", "reuse+preempt"])
def test_audit_mode_is_bit_identical(kw, sparsity):
    _, _, base = _run(sparsity=sparsity, **kw)
    eng, rep, toks = _run(sparsity=sparsity, audit=True, **kw)
    assert toks == base
    au = rep["lifecycle"]["audit"]
    assert au["enabled"] and au["steps_checked"] > 0
    _assert_clean(eng)


# ------------------------------------------------- per-fault recovery ----


def _plan(kind):
    p = FaultPlan(seed=11)
    if kind == "page_squeeze":
        return p.page_squeeze(step=4, pages=6, duration=5)
    if kind == "force_preempt":
        return p.force_preempt(step=4, count=1)
    if kind == "evict_storm":
        return p.evict_storm(step=5)
    if kind == "nan_logits":
        return p.nan_logits(step=4)
    if kind == "bitflip":
        return p.bitflip(step=5)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["page_squeeze", "force_preempt",
                                  "evict_storm", "nan_logits", "bitflip"])
def test_each_fault_recovers_to_clean_tokens(kind):
    kw = dict(paged=True, page_len=8, prefix_reuse=True, preempt=True,
              prefill_chunk=4)
    _, _, base = _run(**kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # quarantine warnings expected
        eng, rep, toks = _run(audit=True, faults=_plan(kind), **kw)
    fs = rep["lifecycle"]["faults"]
    assert fs["fired"] >= 1, f"{kind} never fired: {fs['log']}"
    assert toks == base, f"{kind}: tokens diverged from clean run"
    for r in eng.requests:
        assert r.state is RequestState.DONE and r.error is None
    _assert_clean(eng)
    if kind in ("nan_logits", "bitflip"):
        lc = rep["lifecycle"]
        assert lc["quarantined"], "corruption was not quarantined"
        for path, reason in lc["quarantined"].items():
            assert "quarantined" in reason
        # the quarantine is mirrored into the fallbacks section
        assert any(k == "head" or k.startswith("quarantine:")
                   for k in rep["fallbacks"])


def test_bitflip_quarantine_lands_in_manifest():
    kw = dict(paged=True, page_len=8, prefill_chunk=4)
    plan = FaultPlan(seed=2).bitflip(step=4, field="bitmap")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng, rep, _ = _run(audit=True, faults=plan, **kw)
    [(path, reason)] = list(rep["lifecycle"]["quarantined"].items())
    if path != "lm_head":
        entry = next(e for e in eng.packed.manifest
                     if e.path == path)
        assert not entry.packed and "quarantined" in entry.reason
        assert entry.layout == "dense" and entry.block is None
        # the leaf really dispatches dense now
        parts = path.split("/")
        assert eng.packed.blocks[parts[1]][parts[2]][parts[3]] is None


def test_bitflip_in_served_values_is_caught_on_xla():
    """On the xla dispatch, which decodes the packed arrays inside every
    served step, a bit flipped in a served tensor's values changes what
    the step computes.  Under ``audit=True`` the auditor attributes it to
    that tensor, quarantines it, and the replay serves the clean run's
    tokens.  (Seed 2 flips an exponent bit; a flip in the low half of an
    f32 mantissa is rounded away by the bf16 matmul.)"""
    kw = dict(sparsity=0.0, impl="xla")
    path = "blocks/b0/mlp/w_down"

    def plan():
        return FaultPlan(seed=2).bitflip(step=4, tensor=path,
                                         field="values")

    probe = ServeEngine(get_smoke_config("olmo-1b"), seed=0, num_slots=2,
                        max_len=64, **kw)
    clean = probe.probe_logits([3, 4])
    plan().fire(probe, 4)
    assert not np.array_equal(probe.probe_logits([3, 4]), clean)

    _, _, base = _run(**kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng, rep, toks = _run(audit=True, faults=plan(), **kw)
    assert toks == base
    assert list(rep["lifecycle"]["quarantined"]) == [path]
    assert rep["lifecycle"]["faults"]["log"][0]["tensor"] == path


def test_combined_chaos_gemma_moe():
    """The whole seeded chaos schedule on a second arch (MoE): typed
    terminal states, clean-run tokens, zero violations, zero leaks."""
    kw = dict(arch="gemma3-4b", paged=True, page_len=8,
              prefix_reuse=True, preempt=True, prefill_chunk=4)
    _, _, base = _run(**kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng, rep, toks = _run(audit=True,
                              faults=FaultPlan.chaos(seed=3, horizon=24),
                              **kw)
    assert rep["lifecycle"]["faults"]["fired"] >= 3
    assert toks == base
    for r in eng.requests:
        assert r.terminal
    _assert_clean(eng)


def test_audit_off_nan_serves_garbage():
    """The contrast case: the same NaN'd LM head without the auditor
    silently diverges — detection + quarantine is what buys the
    bit-identical recovery above."""
    kw = dict(paged=True, page_len=8, prefill_chunk=4)
    _, _, base = _run(**kw)
    _, _, toks = _run(faults=FaultPlan(seed=7).nan_logits(step=3), **kw)
    assert toks != base, "NaN head should corrupt unaudited output"


def test_fault_plan_is_deterministic():
    p1 = FaultPlan.chaos(seed=9, horizon=30)
    p2 = FaultPlan.chaos(seed=9, horizon=30)
    assert [(f.step, f.kind) for f in p1.faults] == \
        [(f.step, f.kind) for f in p2.faults]
    kw = dict(paged=True, page_len=8, prefix_reuse=True, preempt=True,
              prefill_chunk=4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, r1, t1 = _run(audit=True, faults=p1, **kw)
        _, r2, t2 = _run(audit=True, faults=p2, **kw)
    assert t1 == t2
    assert r1["lifecycle"]["faults"]["log"] == \
        r2["lifecycle"]["faults"]["log"]
