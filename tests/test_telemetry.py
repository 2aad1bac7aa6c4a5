"""Serving telemetry: registry, spans, events, and the off==on contract.

Pillars:

* **registry units** — Counter/Gauge/Histogram semantics, render order,
  the NaN-free JSON snapshot, and the Prometheus text page;
* **clock regression** — the serving clock starts once, after warmup,
  via the one idempotent ``Clock.start()``: host time spent *before*
  the run (the old double-``_t0``-reset warmup-leak surface) never
  lands in ``report()["wall_s"]``;
* **schema snapshot** — ``report()`` rendered from the registry keeps
  every pre-existing section and field with unchanged names and types
  across the knob matrix ({paged, prefix_reuse, preempt, audit} ×
  {packed, dense}), so downstream bench parsers can't silently break;
* **off == on** — telemetry-off holds no histogram/event objects and
  serves bit-identical tokens to a telemetry-on run of the same trace;
* **profiler spans** — one ``serve.step`` per ``step()`` call with its
  ``serve.<phase>`` spans nested in it, with telemetry off (a caller's
  profiler session) and on (``trace_out``), and the serving programs
  carry the ``attention`` / ``ffn`` / ``lm_head`` scopes in their
  ``op_name`` metadata;
* **artifacts** — the profiler trace reads back (phases nest in steps,
  no overlap), the JSONL event log matches the schema with monotonic
  timestamps, and ``_bench_io`` merges sections atomically without
  clobbering its neighbours.
"""
import json
import math
import pathlib
import re
import sys
import time

import jax
import pytest

from repro.configs import get_smoke_config
from repro.serve import (Clock, MetricsRegistry, ServeEngine,
                         poisson_trace, validate_events)
from repro.serve.telemetry import (EVENT_KINDS, PHASES, read_steps,
                                   validate_event)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "benchmarks"))
import _bench_io  # noqa: E402

CFG = get_smoke_config("olmo-1b")


def _engine(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("sparsity", 0.5)
    return ServeEngine(CFG, seed=0, **kw)


def _run(eng, requests=3, seed=0):
    trace = poisson_trace(requests, rate=0.5, seed=seed,
                          vocab_size=CFG.vocab_size, prompt_len=(1, 4),
                          max_new=(2, 5))
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**spec)
        rep = eng.run()
    return rep, [(r.rid, r.state.name, list(r.tokens))
                 for r in eng.requests]


# ------------------------------------------------------- registry units ----

def test_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c", help="a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(AssertionError):
        c.inc(-1)

    g = reg.gauge("g")
    g.set(7)
    assert g.value == 7
    backing = {"v": 1}
    gf = reg.gauge("gf", lambda: backing["v"])
    backing["v"] = 42
    assert gf.value == 42          # callback gauges are never stale
    with pytest.raises(AssertionError):
        gf.set(0)

    h = reg.histogram("h", seed=3)
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(6.0)
    assert h.mean == pytest.approx(2.0)
    assert h.percentiles()["p50"] == pytest.approx(2.0)

    with pytest.raises(AssertionError):
        reg.counter("c")           # duplicate names are bugs
    assert reg.names == ["c", "g", "gf", "h"]


def test_registry_views_render_in_order():
    reg = MetricsRegistry()
    reg.view("b", lambda: 2)
    reg.view("a", lambda: {"nested": 1})
    assert list(reg.render()) == ["b", "a"]
    assert reg.render()["a"] == {"nested": 1}
    with pytest.raises(AssertionError):
        reg.view("b", lambda: 0)


def test_snapshot_is_strict_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.gauge("reason", lambda: "because")
    reg.histogram("empty")         # NaN percentiles -> None, not NaN
    snap = reg.snapshot()
    assert snap["c"] == 2
    assert snap["reason"] == "because"
    assert snap["empty"]["mean"] is None
    json.dumps(snap, allow_nan=False)   # strict JSON round-trips
    p = tmp_path / "m.json"
    reg.write(str(p))
    doc = json.loads(p.read_text())
    assert doc["schema"] == "repro.serve.metrics/v1"
    assert doc["metrics"]["c"] == 2


def test_prometheus_text_format(tmp_path):
    reg = MetricsRegistry()
    reg.counter("tokens.generated", help="tokens").inc(9)
    reg.gauge("queue.depth", lambda: 3)
    reg.gauge("fallback.reason", lambda: "strings are skipped")
    h = reg.histogram("step.wall_s")
    h.observe(0.25)
    text = reg.to_prometheus()
    assert "# TYPE repro_serve_tokens_generated counter" in text
    assert "repro_serve_tokens_generated 9" in text
    assert "repro_serve_queue_depth 3" in text
    assert "strings are skipped" not in text
    assert 'repro_serve_step_wall_s{quantile="0.5"} 0.25' in text
    assert "repro_serve_step_wall_s_count 1" in text
    p = tmp_path / "m.prom"
    reg.write(str(p))
    assert p.read_text() == text


# ------------------------------------------------------ clock regression ----

def test_clock_starts_once():
    clk = Clock()
    assert not clk.started
    clk.start()
    t0 = clk.t0
    time.sleep(0.01)
    clk.start()                    # idempotent: second start is a no-op
    assert clk.t0 == t0
    assert clk.now() >= 0.0
    assert Clock().now_or_zero() == 0.0


def test_warmup_never_leaks_into_wall(monkeypatch):
    """The regression the one-``Clock`` refactor pins: host time spent
    between warmup and the first step (compile tails, test-harness
    sleeps — anything pre-serving) must not appear in ``wall_s``."""
    eng = _engine()
    with jax.set_mesh(eng.mesh):
        eng.warmup()
        time.sleep(0.3)            # pre-serving dead time
        assert not eng._clock.started
        eng.submit([1, 2, 3], max_new_tokens=2)
        rep = eng.run()
    assert eng._clock.started
    assert rep["wall_s"] < 0.3, (
        f"wall_s={rep['wall_s']:.3f}s includes pre-run dead time")


# -------------------------------------------------------- schema snapshot ----

_PCT = {"p50": float, "p99": float}

# the pre-registry report() layout: every section and field, pinned.
# (value `dict` means "a dict with these exact keys checked recursively";
# a type tuple means isinstance check; None means "may be anything")
_SCHEMA = {
    "requests": int,
    "retained_requests": int,
    "generated_tokens": int,
    "steps": int,
    "wall_s": float,
    "tok_per_s": float,
    "latency_s": _PCT,
    "first_token_s": _PCT,
    "ttft": {"queue_s": _PCT, "prefill_s": _PCT, "first_decode_s": _PCT},
    "prefill": {"enabled": bool, "fallback": None, "prefill_steps": int,
                "decode_steps": int, "chunk": int, "calls": int,
                "tokens_prefilled": int, "in_flight": int,
                "lane_utilization": None},
    "prefix_reuse": {"enabled": bool, "fallback": None,
                     "ttft_hit_s": _PCT, "ttft_miss_s": _PCT,
                     "hit_requests": int, "miss_requests": int,
                     "preempt": {"enabled": bool, "fallback": None,
                                 "count": int, "recomputed_tokens": int}},
    "slot_occupancy": float,
    "weight_sparsity": float,
    "head_compression": float,
    "head_fallback": None,
    "weight_stream": {"packed_tensors": int, "fallback_tensors": int,
                      "sparse_bytes_per_step": (int, float),
                      "dense_bytes_per_step": (int, float),
                      "reduction": float},
    "paging": {"paged": bool, "fallback": None,
               "reserved_kv_bytes": int, "contiguous_kv_bytes": int,
               "reserved_reduction": float},
    "cache_resets": int,
    "lifecycle": {"deadline_ms": None, "max_queue": None,
                  "ttft_budget_ms": None, "max_preempts": int,
                  "cancelled": int, "expired": int, "shed": int,
                  "forced_preempts": int, "wasted_tokens": int,
                  "estimated_ttft_s": None, "terminal_states": dict,
                  "quarantined": dict},
    "fallbacks": dict,
}


def _check(section, spec, path=""):
    if spec is None:
        return
    if isinstance(spec, dict):
        assert isinstance(section, dict), f"{path}: not a section"
        for key, sub in spec.items():
            assert key in section, f"{path}.{key}: field missing"
            _check(section[key], sub, f"{path}.{key}")
        return
    # bools are ints in python; pin them apart so flags stay flags
    if spec is int:
        assert (isinstance(section, int)
                and not isinstance(section, bool)), \
            f"{path}: {type(section).__name__} != int"
    elif spec is float:
        # NaN is legal (empty-histogram percentiles pre-date the
        # registry); the *type* is what downstream parsers rely on
        assert isinstance(section, float), \
            f"{path}: {type(section).__name__} != float"
    else:
        assert isinstance(section, spec), \
            f"{path}: {type(section).__name__} != {spec}"


_KNOBS = [
    {},
    {"paged": True, "page_len": 8},
    {"paged": True, "page_len": 8, "prefill_chunk": 8,
     "prefix_reuse": True},
    {"paged": True, "page_len": 8, "preempt": True},
    {"audit": True},
]


@pytest.mark.parametrize("stream", [True, False],
                         ids=["packed", "dense"])
@pytest.mark.parametrize("knobs", _KNOBS,
                         ids=["plain", "paged", "prefix", "preempt",
                              "audit"])
def test_report_schema_survives_registry(knobs, stream):
    eng = _engine(stream_weights=stream, bitmap_head=stream, **knobs)
    rep, _ = _run(eng, requests=2)
    assert list(rep) == list(_SCHEMA), "top-level keys or order changed"
    _check(rep, _SCHEMA)
    if knobs.get("audit"):
        assert rep["lifecycle"]["audit"]["steps_checked"] > 0
    if knobs.get("paged"):
        assert rep["paging"]["paged"] is True
        assert "fragmentation" in rep["paging"]
    if knobs.get("prefix_reuse"):
        assert "hits" in rep["prefix_reuse"]


# ------------------------------------------------------------ off == on ----

def test_telemetry_off_is_allocation_free_and_identical(tmp_path):
    eng_off = _engine(paged=True, page_len=8, prefill_chunk=8,
                      prefix_reuse=True, audit=True)
    assert eng_off.telemetry is None
    assert eng_off.spans is None and eng_off.events is None
    _, served_off = _run(eng_off, requests=4)

    eng_on = _engine(paged=True, page_len=8, prefill_chunk=8,
                     prefix_reuse=True, audit=True,
                     trace_out=str(tmp_path / "trace"),
                     events_out=str(tmp_path / "e.jsonl"),
                     metrics_out=str(tmp_path / "m.json"))
    try:
        _, served_on = _run(eng_on, requests=4)
    finally:
        paths = eng_on.close()
    assert served_on == served_off
    assert [pathlib.Path(p).name for p in paths] == \
        ["trace", "e.jsonl", "m.json"]
    assert eng_on.close() == []    # idempotent

    steps = read_steps(str(tmp_path / "trace"))
    assert len(steps) == eng_on.spans.steps > 0
    phase_ns = sum(p[2] for s in steps for p in s["phases"])
    assert phase_ns > 0.5 * sum(s["dur_ns"] for s in steps)
    n = validate_events(str(tmp_path / "e.jsonl"))
    assert n > 0


def test_trace_spans_and_phases(tmp_path):
    eng = _engine(prefill_chunk=8, trace_out=str(tmp_path / "trace"))
    try:
        _run(eng, requests=3)
    finally:
        eng.close()
    steps = read_steps(str(tmp_path / "trace"))
    names = {p[0] for s in steps for p in s["phases"]}
    assert names <= set(PHASES)
    assert {"schedule", "prefill", "prefill_sync", "decode", "sample",
            "host_sync"} <= names
    # registry histograms accumulated the same spans (step() calls,
    # not report()["steps"] — that gauge includes idle fast-forward)
    h = eng.metrics.get("step.wall_s")
    assert h.count == eng.spans.steps == len(steps) > 0
    for p in PHASES:
        assert eng.metrics.get(f"step.phase.{p}_s").count == sum(
            q[0] == p for s in steps for q in s["phases"])
    # the phases account for most of the step wall
    phase_s = sum(eng.metrics.get(f"step.phase.{p}_s").sum for p in PHASES)
    assert phase_s > 0.5 * h.sum


@pytest.mark.parametrize("telemetry", [False, True], ids=["off", "on"])
def test_phase_spans_nest_in_one_step_per_call(tmp_path, telemetry):
    """One ``serve.step`` span per ``step()`` call, numbered by the
    engine's step, each phase span inside exactly one of them — whether
    the engine runs its own profiler session (``trace_out``) or a caller
    does with telemetry off."""
    trace_dir = str(tmp_path / "trace")
    eng = _engine(paged=True, page_len=8, prefill_chunk=8,
                  **({"trace_out": trace_dir} if telemetry else {}))
    trace = poisson_trace(3, rate=0.5, seed=1, vocab_size=CFG.vocab_size,
                          prompt_len=(1, 12), max_new=(2, 5))
    numbers = []
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**{**spec, "arrival": 0})
        eng.warmup()
        if not telemetry:
            jax.profiler.start_trace(trace_dir)
        try:
            while eng.scheduler.has_work:
                numbers.append(eng._steps)
                eng.step()
        finally:
            if telemetry:
                eng.close()
            else:
                jax.profiler.stop_trace()
    steps = read_steps(trace_dir)
    assert [s["step"] for s in steps] == numbers
    assert all(s["phases"] and s["phases"][0][0] == "schedule"
               for s in steps)
    names = {p[0] for s in steps for p in s["phases"]}
    assert {"prefill", "prefill_sync", "page_ensure", "decode",
            "host_sync", "sample"} <= names <= set(PHASES)


@pytest.mark.parametrize("program,scopes", [
    ("decode", ("attention", "ffn", "lm_head")),
    ("prefill", ("attention", "ffn"))])
def test_programs_carry_named_scopes(program, scopes):
    """The compiled decode and prefill programs name their layers in the
    ``op_name`` metadata the profiler attaches to each device op."""
    eng = _engine(paged=True, page_len=8, prefill_chunk=8)
    with jax.set_mesh(eng.mesh):
        hlo = eng.lower(program).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope
    if program == "prefill":
        assert not any("/lm_head/" in n for n in names)


def test_event_log_schema(tmp_path):
    path = str(tmp_path / "e.jsonl")
    eng = _engine(deadline_ms=1e9, events_out=path)
    _run(eng, requests=3)
    eng.close()
    n = validate_events(path)
    assert n > 0
    kinds = set()
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            kinds.add(rec["kind"])
            assert rec["kind"] in EVENT_KINDS
    assert {"submit", "admit", "first_token", "done"} <= kinds
    with pytest.raises(ValueError):
        validate_event({"t": 0.0, "step": 0, "kind": "nope"})
    with pytest.raises(ValueError):
        validate_event({"step": 0, "kind": "done"})


# -------------------------------------------------------------- bench io ----

def test_bench_io_merge_preserves_sections(tmp_path):
    path = str(tmp_path / "BENCH.json")
    _bench_io.merge_section(path, "paging", {"x": 1}, verbose=False)
    _bench_io.merge_section(path, "prefill", {"y": 2}, wall_s=1.5,
                            verbose=False)
    doc = _bench_io.load_bench(path)
    assert doc["paging"] == {"x": 1}           # neighbour preserved
    assert doc["prefill"] == {"y": 2, "bench_wall_s": 1.5}
    assert not list(tmp_path.glob(".bench_*")), "tempfile left behind"


def test_bench_timer_records_registry(tmp_path):
    reg = MetricsRegistry()
    with _bench_io.bench_timer("demo", registry=reg) as timing:
        time.sleep(0.01)
    assert timing.wall_s >= 0.01
    h = reg.get("bench.demo.wall_s")
    assert h.count == 1 and h.sum == pytest.approx(timing.wall_s)
    with _bench_io.bench_timer("demo", registry=reg):
        pass
    assert h.count == 2                        # same histogram reused
