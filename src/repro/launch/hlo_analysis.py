"""Compiled-HLO analysis: collective traffic + roofline terms.

``collective_bytes`` parses the post-SPMD optimized HLO text and sums the
result-shape bytes of every collective op (per-device view).  Wire-traffic
factors (ring algorithms, large-group limit): all-reduce counts 2×, the
rest 1×.  ``roofline`` turns (flops, hbm bytes, collective bytes) into the
three per-device time terms for a chip named by its ``device_kind``.
"""
from __future__ import annotations

import re
from typing import Dict

import jax

from repro.launch.hlo_shapes import shape_bytes

#: ``device_kind`` JAX reports for a TPU v5e chip
V5E = "TPU v5 lite"

#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, HBM at
#: 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 1600e9 / 8},
}


def projection_target() -> str:
    """The ``device_kind`` a roofline projection describes: the chip this
    process runs on, or — on a CPU run, which has no roofline of its
    own — the v5e, named explicitly."""
    dev = jax.devices()[0]
    return V5E if dev.platform == "cpu" else dev.device_kind


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table entry for ``device_kind``; a chip the table does
    not hold is an error, never a default."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_WIRE_FACTOR = {
    "all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind result-byte totals + wire-adjusted sum."""
    out: Dict[str, float] = {k: 0.0 for k in _WIRE_FACTOR}
    counts: Dict[str, int] = {k: 0 for k in _WIRE_FACTOR}
    for m in _COLL_RE.finditer(hlo_text):
        shp, kind = m.group(1), m.group(2)
        # -done ops repeat the -start result; count starts & sync forms only
        before = hlo_text[max(0, m.start() - 0):m.end()]
        if "-done(" in before[-60:]:
            continue
        out[kind] += shape_bytes(shp)
        counts[kind] += 1
    wire = sum(out[k] * _WIRE_FACTOR[k] for k in out)
    return {**{f"{k}_bytes": v for k, v in out.items()},
            **{f"{k}_count": c for k, c in counts.items()},
            "wire_bytes": wire}


def roofline(flops: float, hbm_bytes: float, wire_bytes: float,
             device_kind: str) -> Dict[str, float]:
    """Three per-device roofline time terms (seconds) + the bottleneck,
    against the peaks of ``device_kind``."""
    pk = peaks(device_kind)
    t_compute = flops / pk["flops"]
    t_memory = hbm_bytes / pk["hbm_bw"]
    t_collective = wire_bytes / pk["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    bound = max(terms, key=terms.get)
    t_max = terms[bound]
    t_sum = t_compute + t_memory + t_collective
    return {
        **terms,
        "device_kind": device_kind,
        "bottleneck": bound.replace("_s", ""),
        # fraction of the ideal overlapped step this term would allow
        "roofline_fraction_overlap": t_max / t_sum if t_sum else 0.0,
        "step_time_overlapped_s": t_max,
    }
