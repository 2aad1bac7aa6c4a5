"""Memory-traffic observatory tests (serve/traffic.py).

The load-bearing guarantees:

* **ledger == weight_stream, to the byte** — the per-role attribution
  reuses the manifest's exact per-entry accounting, so its sums equal
  the ``weight_stream`` aggregates exactly (packed and dense-baseline,
  MoE activated-expert scaling included), and stay equal after a
  quarantine flips entries to dense;
* **modeled-vs-compiled** — the cross-check lowers the engine's real
  jitted decode/prefill steps, counts bytes with the while-aware HLO
  analyzer, and the ratio against the modeled fetch floor sits inside
  the per-phase tolerance band across {packed, dense} × {contig,
  paged} on two archs (this is also the hlo_counters real-step
  coverage the synthetic GEMM/scan tests don't give);
* **off == on** — a ``traffic_out``-less engine serves bit-identical
  tokens and holds no artifact state; the ledger's counters live in
  the always-on registry like every other subsystem's;
* the registry's per-phase byte counters reconcile with the report and
  with the profiler trace's ``serve.decode`` / ``serve.prefill`` spans,
  and the artifact round-trips through ``scripts/traffic_report.py``'s
  budget gate.
"""
import json
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.serve import ServeEngine, poisson_trace
from repro.serve.telemetry import read_steps
from repro.serve.traffic import (CROSSCHECK_BANDS, TRAFFIC_KINDS,
                                 TRAFFIC_PHASES, role_of)

ARCHS = ["olmo-1b", "granite-moe-3b-a800m"]

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _engine(arch="olmo-1b", **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("sparsity", 0.5)
    return ServeEngine(get_smoke_config(arch), seed=0, **kw)


def _run(eng, requests=3, seed=0):
    trace = poisson_trace(requests, rate=0.5, seed=seed,
                          vocab_size=eng.cfg.vocab_size,
                          prompt_len=(1, 4), max_new=(2, 5))
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**spec)
        rep = eng.run()
    return rep, [(r.rid, r.state.name, list(r.tokens))
                 for r in eng.requests]


# ------------------------------------------------------------ role map ----

def test_role_of():
    assert role_of("blocks/b0/attn/wq") == "attn.wq"
    assert role_of("blocks/b0/attn/wo") == "attn.wo"
    assert role_of("blocks/b0/attn/norm") == "norm"
    assert role_of("blocks/b0/mlp/w_up") == "mlp"
    assert role_of("blocks/b0/moe/router") == "moe.router"
    assert role_of("blocks/b0/moe/w_gate") == "moe.experts"
    assert role_of("blocks/b0/mamba/in_proj") == "ssm"
    assert role_of("blocks/b0/rwkv/wk") == "ssm"


# --------------------------------------------- ledger == weight_stream ----

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("stream", [True, False],
                         ids=["packed", "dense"])
def test_ledger_sums_exactly_to_weight_stream(arch, stream):
    eng = _engine(arch, stream_weights=stream, bitmap_head=stream)
    rep, _ = _run(eng)
    ws, roles = rep["weight_stream"], rep["traffic"]["per_role"]
    assert sum(r["sparse_bytes"] for r in roles.values()) \
        == ws["sparse_bytes_per_step"]
    assert sum(r["dense_bytes"] for r in roles.values()) \
        == ws["dense_bytes_per_step"]
    w = rep["traffic"]["weight"]
    assert w["sparse_bytes_per_step"] == ws["sparse_bytes_per_step"]
    assert w["dense_bytes_per_step"] == ws["dense_bytes_per_step"]
    assert w["reduction"] == pytest.approx(ws["reduction"])
    # roles carry the arch's expected structure
    if eng.cfg.num_experts:
        assert "moe.experts" in roles and "moe.router" in roles
    assert "head" in roles


def test_ledger_tracks_quarantine():
    eng = _engine()
    before = eng.traffic.per_role()
    path = next(e for e in eng.packed.manifest if e.packed).path
    eng.packed.quarantine(path, "test")
    eng.traffic.invalidate()
    after = eng.traffic.per_role()
    role = role_of(path)
    assert after[role]["sparse_bytes"] > before[role]["sparse_bytes"]
    # the exactness pin must survive the quarantine
    ws = eng.weight_stream_report()
    assert sum(r["sparse_bytes"] for r in after.values()) \
        == ws["sparse_bytes_per_step"]


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


# ----------------------------------- modeled vs compiled (hlo_counters) ----

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("knobs", [
    {"stream_weights": True, "bitmap_head": True},
    {"stream_weights": False, "bitmap_head": False},
    {"stream_weights": True, "bitmap_head": True,
     "paged": True, "page_len": 8},
    {"stream_weights": True, "bitmap_head": True,
     "paged": True, "page_len": 8, "prefill_chunk": 8},
], ids=["packed-contig", "dense-contig", "packed-paged",
        "packed-paged-prefill"])
def test_crosscheck_within_band(arch, knobs):
    eng = _engine(arch, **knobs)
    cc = eng.traffic.crosscheck()
    assert cc["dispatch"] in ("xla-oracle", "pallas", "dense")
    assert "decode" in cc
    if knobs.get("prefill_chunk"):
        assert "prefill" in cc
    for phase in ("decode", "prefill"):
        if phase not in cc:
            continue
        e = cc[phase]
        lo, hi = CROSSCHECK_BANDS[phase]
        assert e["compiled_bytes"] > 0
        assert e["compiled_flops"] > 0
        # the modeled side is a fetch floor: compiled can only exceed it
        assert e["ratio"] >= lo, (phase, e)
        assert e["ratio"] <= hi, (phase, e)
        assert e["within_band"]
        assert e["modeled"]["total_bytes"] \
            == (e["modeled"]["weight_bytes"] + e["modeled"]["head_bytes"]
                + e["modeled"]["kv_bytes"])
    # the cached verdict surfaces in report()
    assert eng.report()["traffic"]["crosscheck"] is cc


def test_crosscheck_floor_scales_with_dispatch():
    """The xla-oracle dispatch fetches dense renderings, so its floor
    must sit above the (hypothetical) pallas floor of the same pack."""
    eng = _engine()
    oracle = eng.traffic.modeled_executed("decode")
    sparse_stream = eng.weight_stream_report()["sparse_bytes_per_step"]
    assert oracle["weight_bytes"] + oracle["head_bytes"] > sparse_stream


# -------------------------------------------------------- phase hooks ----

def test_phase_counters_accumulate_and_match_trace(tmp_path):
    trace_dir = str(tmp_path / "trace")
    eng = _engine(paged=True, page_len=8, prefill_chunk=8,
                  trace_out=trace_dir)
    try:
        rep, _ = _run(eng, requests=4)
    finally:
        eng.close()
    ph = rep["traffic"]["phases"]
    assert ph["decode"]["steps"] > 0
    assert ph["decode"]["weight_bytes"] \
        == ph["decode"]["steps"] * rep["traffic"]["weight"][
            "sparse_bytes_per_step"]
    assert ph["prefill"]["calls"] > 0
    assert ph["prefill"]["kv_write_bytes"] > 0
    # prefill streams the stack only (no LM head application)
    stack = (rep["traffic"]["weight"]["sparse_bytes_per_step"]
             - rep["traffic"]["per_role"]["head"]["sparse_bytes"])
    assert ph["prefill"]["weight_bytes"] == ph["prefill"]["calls"] * stack
    for phase in TRAFFIC_PHASES:
        for kind in TRAFFIC_KINDS:
            name = f"traffic.{phase}.{kind}_bytes"
            assert eng.metrics.get(name).value \
                == ph[phase][f"{kind}_bytes"], name
    # one accounted decode step / prefill call per traced phase span
    spans = [p[0] for s in read_steps(trace_dir) for p in s["phases"]]
    assert spans.count("decode") == ph["decode"]["steps"]
    assert spans.count("prefill") == ph["prefill"]["calls"]


def test_registry_counters_registered():
    eng = _engine()
    for phase in TRAFFIC_PHASES:
        for kind in TRAFFIC_KINDS:
            assert f"traffic.{phase}.{kind}_bytes" in eng.metrics.names


# ------------------------------------------------------------ off == on ----

def test_traffic_off_is_identical_and_stateless(tmp_path):
    eng_off = _engine(paged=True, page_len=8, prefill_chunk=8)
    assert eng_off.traffic_out is None
    _, served_off = _run(eng_off, requests=4)
    assert eng_off.close() == []
    assert eng_off.traffic._crosscheck is None   # nothing compiled

    out = tmp_path / "traffic.json"
    eng_on = _engine(paged=True, page_len=8, prefill_chunk=8,
                     traffic_out=str(out))
    _, served_on = _run(eng_on, requests=4)
    assert served_on == served_off
    assert eng_on.close() == [str(out)]
    assert eng_on.close() == []                  # idempotent
    doc = json.loads(out.read_text())
    assert doc["schema"] == "repro.serve.traffic/v1"
    assert doc["traffic"]["crosscheck"]["decode"]["within_band"]


# ------------------------------------------------- energy + roofline ----

def test_energy_and_roofline_projection():
    eng = _engine()
    rep, _ = _run(eng)
    en = rep["traffic"]["energy"]
    assert en["macs_per_token"] > 0
    assert 0 < en["pj_per_token"] < en["pj_per_token_dense"]
    assert en["tops_per_watt"] > en["tops_per_watt_dense"] > 0
    rl = rep["traffic"]["roofline"]
    assert "decode" in rl
    assert rl["decode"]["bottleneck"] in ("compute", "memory",
                                          "collective")
    assert rl["decode"]["memory_s"] > 0
    # a CPU run has no roofline of its own: it names the v5e it projects to
    from repro.launch.hlo_analysis import V5E
    assert rl["decode"]["device_kind"] == V5E


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v4", False), ("cpu", False)])
def test_peaks_table_refuses_unknown_chips(kind, known):
    """Peaks come from the table keyed by ``device_kind``; a chip it does
    not hold is an error, never a silent v5e default."""
    from repro.launch.hlo_analysis import peaks, roofline
    if known:
        assert peaks(kind)["hbm_bw"] == 819e9
        assert roofline(1.0, 819e9, 0.0, kind)["memory_s"] == 1.0
    else:
        with pytest.raises(ValueError, match="no published peaks"):
            roofline(1.0, 1.0, 0.0, kind)


# --------------------------------------------------- tooling round-trip ----

def test_traffic_report_budget_gate(tmp_path):
    out = tmp_path / "traffic.json"
    eng = _engine(traffic_out=str(out))
    _run(eng)
    eng.close()
    budget = tmp_path / "budget.json"
    script = str(_ROOT / "scripts" / "traffic_report.py")
    env_path = str(_ROOT / "src")
    seed = subprocess.run(
        [sys.executable, script, str(out), "--budget", str(budget),
         "--update-budget"],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert seed.returncode == 0, seed.stdout + seed.stderr
    gate = subprocess.run(
        [sys.executable, script, str(out), "--budget", str(budget)],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert gate.returncode == 0, gate.stdout + gate.stderr
    assert "ok" in gate.stdout
    # shrink the budget far below the measured bytes: the gate must fail
    b = json.loads(budget.read_text())
    for entry in b.values():
        for k, v in list(entry.items()):
            if k.endswith("bytes_per_step") or k.endswith("_bytes"):
                entry[k] = int(v * 0.5)
    budget.write_text(json.dumps(b))
    fail = subprocess.run(
        [sys.executable, script, str(out), "--budget", str(budget)],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert fail.returncode == 1
    assert "REGRESSED" in fail.stdout
