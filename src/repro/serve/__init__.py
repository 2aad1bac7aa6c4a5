"""Continuous-batching sparse serving engine (queue, slots, KV reuse,
paged KV cache, chunked batched prefill, whole-stack bitmap weight
streaming, request-lifecycle hardening + fault injection)."""
from repro.serve.cache import SlotKVCache
from repro.serve.engine import ServeEngine, pack_lm_head
from repro.serve.errors import (AuditViolation, DeadlineExceeded,
                                ServeError, ServeOverloaded)
from repro.serve.faults import Fault, FaultPlan, InvariantAuditor
from repro.serve.packed import (PackedModel, PackEntry, choose_block,
                                pack_model)
from repro.serve.paging import (OutOfPages, PagedKVCache, PagePool,
                                PrefixBlock)
from repro.serve.prefill import PrefillJob, PrefillPlanner
from repro.serve.request import (Request, RequestRejected, RequestState,
                                 TERMINAL_STATES)
from repro.serve.scheduler import SlotScheduler
from repro.serve.telemetry import (Clock, Counter, EventLog, Gauge,
                                   Histogram, MetricsRegistry, StepSpans,
                                   Telemetry, validate_events)
from repro.serve.trace import RollingStat, percentiles, poisson_trace

__all__ = [
    "AuditViolation", "Clock", "Counter",
    "DeadlineExceeded", "EventLog", "Fault", "FaultPlan", "Gauge",
    "Histogram", "InvariantAuditor", "MetricsRegistry", "OutOfPages",
    "PackEntry", "PackedModel", "PagePool", "PagedKVCache", "PrefillJob",
    "PrefillPlanner", "PrefixBlock", "Request", "RequestRejected",
    "RequestState", "RollingStat", "ServeEngine", "ServeError",
    "ServeOverloaded", "SlotKVCache", "SlotScheduler", "StepSpans",
    "TERMINAL_STATES", "Telemetry", "choose_block",
    "pack_lm_head", "pack_model", "percentiles", "poisson_trace",
    "validate_events",
]
