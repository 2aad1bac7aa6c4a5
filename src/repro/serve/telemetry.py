"""Serving telemetry: one metrics registry, step-phase profiler spans,
and a structured event log.

The paper's headline claims are *traffic* claims (EIM/SIDR cut SRAM
access 86 % vs SparTen), and EIE-style designs live or die on
per-component access counts made visible, not inferred — so the serving
engine's observability is a subsystem, not an afterthought:

* **Metrics registry** (`MetricsRegistry`): typed ``Counter`` /
  ``Gauge`` / ``Histogram`` metrics (histograms reuse the seeded
  ``RollingStat`` reservoir, so percentiles stay deterministic per
  trace) that every subsystem — engine, scheduler, paging, prefill
  planner, packed stream, faults/audit — registers into.  The engine's
  ``report()`` is a *rendered snapshot* of this one registry: named
  ``view`` entries reproduce the legacy section/field layout
  byte-for-byte (schema pinned by test), while the flat metrics export
  as a Prometheus text page (``to_prometheus()``) or a JSON snapshot
  (``--metrics-out``).

* **Step-phase spans**: every ``ServeEngine.step`` is a
  ``jax.profiler.StepTraceAnnotation`` named ``serve.step`` and each of
  its host-side phases (``PHASES``: schedule / prefill / prefill-sync /
  page-ensure / decode / host-sync / sample / deadline-sweep / audit) a
  ``TraceAnnotation`` ``serve.<phase>`` inside it.  They are recorded
  whenever a profiler session is on — the engine's own (``--trace-out
  DIR``) or any caller's — on the profiler's clock, beside the device's
  operations.  Spans bracket host code only: ``decode`` ends at
  dispatch, and device time surfaces in ``host_sync`` (and
  ``prefill_sync``), the existing block-until-ready points.  With
  telemetry on they also feed the registry's per-phase histograms
  (`StepSpans`).

* **Event log** (`EventLog`): one JSONL schema unifying lifecycle
  transitions, fallback warnings, fault injections and audit
  violations — every record carries a monotonic timestamp, the engine
  step, a ``kind`` from ``EVENT_KINDS`` and (where applicable) the
  rid, so "what happened to request 1234" is one grep.

Telemetry-off is the default and is bit-identical: the engine holds
``spans is None`` / ``events is None``; what remains on the hot path is
one ``TraceAnnotation`` per span, which records nothing (and costs a
few microseconds) while no profiler session is on.

``Clock`` is the serving wall clock: started exactly once, *after*
warmup, through one idempotent ``start()`` — hoisted here from the two
``_t0`` resets the engine used to carry so compile time can never leak
into the first timed step again.
"""
from __future__ import annotations

import glob
import json
import math
import os
import time
from typing import Callable, Dict, List, Optional, Union

from repro.serve.trace import RollingStat

__all__ = [
    "Clock", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "StepSpans", "EventLog", "Telemetry", "PHASES", "STEP_SPAN",
    "SPAN_PREFIX", "EVENT_KINDS", "read_steps", "validate_event",
    "validate_events",
]


# ---------------------------------------------------------------- clock ----

class Clock:
    """The serving wall clock: monotonic (``time.perf_counter``),
    started exactly once via the idempotent ``start()``.

    The engine calls ``start()`` *after* ``warmup()`` in both ``step``
    and ``run`` — one helper instead of the two hand-rolled ``_t0``
    resets it used to carry, so no call path can start the clock while
    XLA is still compiling (the warmup-leak regression test pins this).
    """

    def __init__(self) -> None:
        self._t0: Optional[float] = None

    @property
    def started(self) -> bool:
        return self._t0 is not None

    @property
    def t0(self) -> Optional[float]:
        return self._t0

    def start(self) -> None:
        """Start the clock; later calls are no-ops."""
        if self._t0 is None:
            self._t0 = time.perf_counter()

    def now(self) -> float:
        """Seconds since ``start()``; raises if never started."""
        assert self._t0 is not None, "Clock.now() before start()"
        return time.perf_counter() - self._t0

    def now_or_zero(self) -> float:
        """``now()``, or 0.0 before the clock starts (pre-run events)."""
        return time.perf_counter() - self._t0 if self._t0 is not None \
            else 0.0


# -------------------------------------------------------------- metrics ----

class Counter:
    """Monotonic counter."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.value: Union[int, float] = 0

    def inc(self, n: Union[int, float] = 1) -> None:
        assert n >= 0, f"counter {self.name} decremented by {n}"
        self.value += n


class Gauge:
    """Point-in-time value: either set explicitly (``set``) or computed
    at snapshot time from a callback (``fn``) — subsystems register
    callback gauges over their live state so the registry never holds a
    stale copy.  Values may be non-numeric (fallback-reason strings,
    None); those appear in the JSON snapshot and are skipped by the
    Prometheus exporter."""

    kind = "gauge"

    def __init__(self, name: str, fn: Optional[Callable] = None,
                 help: str = ""):
        self.name = name
        self.help = help
        self._fn = fn
        self._value = None

    def set(self, v) -> None:
        assert self._fn is None, f"gauge {self.name} is callback-backed"
        self._value = v

    @property
    def value(self):
        return self._fn() if self._fn is not None else self._value


class Histogram:
    """Distribution metric over the seeded ``RollingStat`` reservoir:
    exact count/sum/mean, deterministic p50/p99 (exact below the
    reservoir cap — identical to a full scan on short traces)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", cap: int = 2048,
                 seed: int = 0):
        self.name = name
        self.help = help
        self.stat = RollingStat(cap=cap, seed=seed)

    def observe(self, v) -> None:
        self.stat.add(v)

    @property
    def count(self) -> int:
        return self.stat.count

    @property
    def sum(self) -> float:
        return self.stat.total

    @property
    def mean(self) -> float:
        return self.stat.mean

    def percentiles(self, qs=(50, 99)) -> Dict[str, float]:
        return self.stat.percentiles(qs)


def _nan_to_none(v):
    return None if isinstance(v, float) and math.isnan(v) else v


def prom_name(name: str, prefix: str = "repro_serve_") -> str:
    """Sanitize a dotted metric name into Prometheus form."""
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    return prefix + out


class MetricsRegistry:
    """The one place metrics live.

    Two faces:

    * flat typed metrics (``counter`` / ``gauge`` / ``histogram``),
      snapshot-exported as JSON (``snapshot``/``write``) or a
      Prometheus text page (``to_prometheus``);
    * named ``view`` entries — callables evaluated at render time —
      whose insertion-ordered evaluation *is* the engine's
      ``report()`` dict, so the legacy report schema is a rendered
      projection of the registry rather than a second bookkeeping
      system.
    """

    def __init__(self) -> None:
        self._metrics: Dict[str, Union[Counter, Gauge, Histogram]] = {}
        self._views: Dict[str, Callable[[], object]] = {}

    # ---- registration -------------------------------------------------

    def _add(self, metric):
        assert metric.name not in self._metrics, \
            f"duplicate metric {metric.name}"
        self._metrics[metric.name] = metric
        return metric

    def counter(self, name: str, help: str = "") -> Counter:
        return self._add(Counter(name, help))

    def gauge(self, name: str, fn: Optional[Callable] = None,
              help: str = "") -> Gauge:
        return self._add(Gauge(name, fn, help))

    def histogram(self, name: str, help: str = "", cap: int = 2048,
                  seed: int = 0) -> Histogram:
        return self._add(Histogram(name, help, cap=cap, seed=seed))

    def view(self, name: str, fn: Callable[[], object]) -> None:
        """Register a top-level ``report()`` entry (scalar or section)."""
        assert name not in self._views, f"duplicate view {name}"
        self._views[name] = fn

    def get(self, name: str):
        return self._metrics[name]

    @property
    def names(self) -> List[str]:
        return list(self._metrics)

    # ---- rendering ----------------------------------------------------

    def render(self) -> Dict:
        """Evaluate every view in registration order — the report."""
        return {name: fn() for name, fn in self._views.items()}

    def snapshot(self) -> Dict:
        """Flat ``{name: value}`` snapshot of every metric.  Histograms
        render as ``{count, sum, mean, p50, p99}``; NaN (empty
        histogram) becomes None so the snapshot is strict JSON."""
        out: Dict[str, object] = {}
        for name, m in self._metrics.items():
            if isinstance(m, Histogram):
                pct = m.percentiles()
                out[name] = {
                    "count": m.count, "sum": m.sum,
                    "mean": _nan_to_none(m.mean),
                    "p50": _nan_to_none(pct["p50"]),
                    "p99": _nan_to_none(pct["p99"]),
                }
            else:
                out[name] = _nan_to_none(m.value)
        return out

    def to_prometheus(self, prefix: str = "repro_serve_") -> str:
        """Prometheus text exposition (0.0.4).  Counters and numeric
        gauges export directly; histograms export as summaries
        (quantile-labelled samples + ``_sum``/``_count``); non-numeric
        gauges (reason strings, None) are skipped — they live in the
        JSON snapshot and the rendered report."""
        lines: List[str] = []
        for name, m in self._metrics.items():
            pname = prom_name(name, prefix)
            if isinstance(m, Histogram):
                if m.help:
                    lines.append(f"# HELP {pname} {m.help}")
                lines.append(f"# TYPE {pname} summary")
                pct = m.percentiles()
                for q, key in ((0.5, "p50"), (0.99, "p99")):
                    v = pct[key]
                    if not math.isnan(v):
                        lines.append(f'{pname}{{quantile="{q}"}} {v}')
                lines.append(f"{pname}_sum {m.sum}")
                lines.append(f"{pname}_count {m.count}")
                continue
            v = m.value
            if isinstance(v, bool):
                v = int(v)
            if not isinstance(v, (int, float)):
                continue
            if m.help:
                lines.append(f"# HELP {pname} {m.help}")
            lines.append(f"# TYPE {pname} {m.kind}")
            lines.append(f"{pname} {v}")
        return "\n".join(lines) + "\n"

    def write(self, path: str) -> None:
        """Write the snapshot: Prometheus text for ``.prom`` paths,
        strict JSON otherwise."""
        if path.endswith(".prom"):
            with open(path, "w") as f:
                f.write(self.to_prometheus())
            return
        with open(path, "w") as f:
            json.dump({"schema": "repro.serve.metrics/v1",
                       "metrics": self.snapshot()}, f, indent=2,
                      allow_nan=False)


# ----------------------------------------------------------- step spans ----

#: The step-phase taxonomy (DESIGN_SERVING.md §Observability).  Phases
#: are sequential and non-overlapping inside one step; together they
#: cover (nearly) the whole host-side step wall.  Each is a profiler span
#: ``serve.<phase>`` inside the step's ``serve.step`` span.
PHASES = ("schedule", "prefill", "prefill_sync", "page_ensure", "decode",
          "host_sync", "sample", "deadline_sweep", "audit")

#: Profiler span names: one ``STEP_SPAN`` per ``ServeEngine.step`` call
#: (a ``jax.profiler.StepTraceAnnotation`` carrying ``step_num``), and
#: ``SPAN_PREFIX + phase`` for each phase inside it.
STEP_SPAN = "serve.step"
SPAN_PREFIX = "serve."

_PHASE_SEEDS = {p: 0x7e1e + i for i, p in enumerate(PHASES)}


class StepSpans:
    """The registry histograms that the engine's phase spans feed when
    telemetry is on: ``step.phase.<p>_s`` per phase and ``step.wall_s``
    per step, all host seconds on ``time.perf_counter``."""

    def __init__(self, registry: MetricsRegistry):
        self.h_phase = {
            p: registry.histogram(
                f"step.phase.{p}_s", seed=_PHASE_SEEDS[p],
                help=f"host-side seconds in the {p} phase per step")
            for p in PHASES}
        self.h_step = registry.histogram(
            "step.wall_s", seed=0x57e9,
            help="host-side wall seconds per engine step")
        self.steps = 0

    def phase_done(self, name: str, seconds: float) -> None:
        self.h_phase[name].observe(seconds)

    def step_done(self, seconds: float) -> None:
        self.h_step.observe(seconds)
        self.steps += 1


def read_steps(trace_dir: str) -> List[Dict]:
    """The ``serve.step`` spans of the newest profiler trace under
    ``trace_dir`` in time order, each as ``{"step", "start_ns",
    "dur_ns", "phases": [(name, start_ns, dur_ns), ...]}`` with the
    ``serve.<phase>`` spans (names without the prefix) nested in it.

    Raises ``ValueError`` unless every phase span nests in exactly one
    step span and the phases of one step do not overlap — the contract
    the CI smoke checks."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    steps, phases = [], []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == STEP_SPAN:
                    steps.append({"step": dict(e.stats).get("step_num"),
                                  "start_ns": e.start_ns,
                                  "dur_ns": e.duration_ns, "phases": []})
                elif e.name.startswith(SPAN_PREFIX):
                    phases.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                   e.duration_ns))
    steps.sort(key=lambda s: s["start_ns"])
    for ph in phases:
        _, t0, dur = ph
        hosts = [s for s in steps if s["start_ns"] <= t0
                 and t0 + dur <= s["start_ns"] + s["dur_ns"]]
        if len(hosts) != 1:
            raise ValueError(f"phase span {ph} nests in {len(hosts)} "
                             f"step spans, not one")
        hosts[0]["phases"].append(ph)
    for s in steps:
        s["phases"].sort(key=lambda p: p[1])
        for a, b in zip(s["phases"], s["phases"][1:]):
            if a[1] + a[2] > b[1]:
                raise ValueError(f"phases {a[0]} and {b[0]} overlap inside "
                                 f"step {s['step']}")
    return steps


# ------------------------------------------------------------ event log ----

#: The unified event schema's ``kind`` vocabulary: request lifecycle
#: transitions, the fallback/fault/quarantine surface, and audit
#: violations — one stream, one set of field names.
EVENT_KINDS = frozenset({
    "submit", "admit", "prefill_done", "first_token", "preempt",
    "done", "cancelled", "expired", "shed", "fallback", "fault",
    "quarantine", "audit_violation",
})

_REQUIRED = ("t", "step", "kind")


def validate_event(rec: Dict) -> None:
    """Raise ``ValueError`` unless ``rec`` matches the event schema:
    ``t`` (float seconds, monotonic per log), ``step`` (int >= 0),
    ``kind`` (one of ``EVENT_KINDS``), ``rid`` (int or None), and
    JSON-scalar extras."""
    for key in _REQUIRED:
        if key not in rec:
            raise ValueError(f"event missing required field {key!r}: "
                             f"{rec}")
    if not isinstance(rec["t"], (int, float)) or rec["t"] < 0:
        raise ValueError(f"event t must be a non-negative number: {rec}")
    if not isinstance(rec["step"], int) or rec["step"] < 0:
        raise ValueError(f"event step must be a non-negative int: {rec}")
    if rec["kind"] not in EVENT_KINDS:
        raise ValueError(f"unknown event kind {rec['kind']!r}: {rec}")
    rid = rec.get("rid")
    if rid is not None and not isinstance(rid, int):
        raise ValueError(f"event rid must be int or None: {rec}")


def validate_events(path: str) -> int:
    """Validate a JSONL event file: every line parses, matches the
    schema, and timestamps are monotonic.  Returns the record count."""
    last_t = -1.0
    n = 0
    with open(path) as f:
        for i, line in enumerate(f):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}")
            validate_event(rec)
            if rec["t"] < last_t:
                raise ValueError(
                    f"{path}:{i + 1}: timestamp went backwards "
                    f"({rec['t']} < {last_t})")
            last_t = rec["t"]
            n += 1
    return n


class EventLog:
    """Structured JSONL event log.  Records buffer in memory (bounded
    by ``cap``: the oldest records drop first, with a counter so the
    truncation is visible) and ``write()`` dumps one JSON object per
    line."""

    def __init__(self, cap: int = 65536):
        from collections import deque
        self.records = deque(maxlen=cap)
        self.emitted = 0
        self.dropped = 0

    def emit(self, kind: str, *, t: float, step: int,
             rid: Optional[int] = None, **fields) -> None:
        assert kind in EVENT_KINDS, f"unknown event kind {kind!r}"
        rec = {"t": t, "step": step, "kind": kind, "rid": rid}
        if fields:
            rec.update(fields)
        if len(self.records) == self.records.maxlen:
            self.dropped += 1
        self.records.append(rec)
        self.emitted += 1

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            for rec in self.records:
                f.write(json.dumps(rec, allow_nan=False) + "\n")


# ------------------------------------------------------------ telemetry ----

class Telemetry:
    """The engine's telemetry bundle: the phase histograms (always, when
    telemetry is on), an event log when ``events_out`` is set, and a
    profiler session writing to the directory ``trace_out`` when that is
    set.  ``close()`` writes every configured artifact (idempotent)."""

    def __init__(self, registry: MetricsRegistry, *,
                 trace_out: Optional[str] = None,
                 events_out: Optional[str] = None,
                 metrics_out: Optional[str] = None):
        self.registry = registry
        self.trace_out = trace_out
        self.events_out = events_out
        self.metrics_out = metrics_out
        self.events = EventLog() if events_out else None
        self.spans = StepSpans(registry)
        self._tracing = False
        self._closed = False

    def start(self) -> None:
        """Open the profiler session for ``trace_out`` (once; the engine
        calls this when it starts serving, after warmup).  The trace holds
        the engine's ``serve.*`` spans and the device's operations on one
        clock; Perfetto opens the ``perfetto_trace.json.gz`` it writes."""
        if self.trace_out and not self._tracing and not self._closed:
            import jax
            jax.profiler.start_trace(self.trace_out,
                                     create_perfetto_trace=True)
            self._tracing = True

    def close(self) -> List[str]:
        """Stop the profiler session and write every configured artifact;
        returns the paths written.  Safe to call more than once (later
        calls are no-ops)."""
        if self._closed:
            return []
        self._closed = True
        written = []
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            written.append(self.trace_out)
        for path, fn in ((self.events_out, lambda p: self.events.write(p)),
                         (self.metrics_out, self.registry.write)):
            if path:
                d = os.path.dirname(path)
                if d:
                    os.makedirs(d, exist_ok=True)
                fn(path)
                written.append(path)
        return written
