"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* busy: the union of the intervals in which an operation ran on a device
  (the device plane's ``XLA Ops`` line), clipped to the traced window and
  averaged over the devices;
* per program: the device time of each execution of a jitted program
  (``XLA Modules`` line), keyed by the program's name without the
  ``jit_`` prefix and the ``(id)`` suffix;
* per kernel: the summed device time of the operations named after the
  kernel (``bitmap_spmm.<n>``);
* the breakdown: the device operations that took most time, by name
  without the instance number and counting only operations that hold no
  other, and the longest idle gaps, each named by the innermost
  ``bench.*`` host span that covers its midpoint.

Operations and programs count only when they lie wholly inside the
window; busy time is clipped to it.

The traced window is the host span ``bench.window`` that the harness
writes around its measured window; without one, the device's first and
last events bound it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


Planes = Dict[str, Dict[str, List[Event]]]   # plane -> line -> events


def from_profile(pd) -> Planes:
    """Convert ``jax.profiler.ProfileData`` into plain events."""
    out: Planes = {}
    for plane in pd.planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                evs.append(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns)))
        out[plane.name] = lines
    return out


def load(trace_dir: str) -> Planes:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def device_planes(planes: Planes) -> List[str]:
    """The accelerator cores' planes (``/device:TPU:<n>``), not the
    profiler's own custom planes."""
    return sorted(p for p in planes if re.fullmatch(r"/device:TPU:\d+", p))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def host_spans(planes: Planes) -> List[Event]:
    """Every ``bench.*`` span on the host planes."""
    out = []
    for name, lines in planes.items():
        if name.startswith("/device:"):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e.name.startswith("bench."))
    return out


def window(planes: Planes) -> Tuple[float, float]:
    spans = [e for e in host_spans(planes) if e.name == WINDOW_SPAN]
    if spans:
        w = max(spans, key=lambda e: e.dur_ns)
        return w.start_ns, w.end_ns
    evs = [e for p in device_planes(planes)
           for line in planes[p].values() for e in line]
    if not evs:
        raise ValueError("the trace holds no device events")
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def _ops(lines: Dict[str, List[Event]]) -> List[Event]:
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    return [e for n, evs in lines.items()
            if n not in ("XLA Modules", "Steps") for e in evs]


def program_name(name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """The HLO instruction's own name: the TPU trace names an operation
    by its whole HLO text, ``%bitmap_spmm.63 = bf16[...] custom-call(...)``."""
    m = re.match(r"\s*%?([\w.\-]+)\s*=", name)
    return m.group(1) if m else name.strip()


def op_family(name: str) -> str:
    """An operation's name without its numeric instance suffix."""
    n = op_name(name)
    return re.sub(r"[.\d]+$", "", n) or n


def _leaves(ops: List[Event]) -> List[Event]:
    """Operations that hold no other operation of the line (a ``while``
    or ``call`` spans the operations of its body)."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, e in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt.start_ns < e.end_ns
                               and nxt.end_ns <= e.end_ns):
            out.append(e)
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    programs: Dict[str, List[float]]      # name -> device seconds per run
    kernels: Dict[str, float]             # name -> summed device seconds
    kernel_calls: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(planes: Planes, kernels: Tuple[str, ...] = (),
           top: int = 10) -> Reduction:
    lo, hi = window(planes)
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy_total = 0.0
    programs: Dict[str, List[float]] = {}
    ksum = {k: 0.0 for k in kernels}
    kcalls = {k: 0 for k in kernels}
    fam: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for p in devs:
        lines = planes[p]
        ops = [e for e in _ops(lines)
               if e.end_ns > lo and e.start_ns < hi]
        busy = _union(_clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        prev = lo
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
        for e in lines.get("XLA Modules", []):
            if e.start_ns >= lo and e.end_ns <= hi:
                programs.setdefault(program_name(e.name), []).append(
                    e.dur_ns * 1e-9)
        inside = [e for e in ops if lo <= e.start_ns and e.end_ns <= hi]
        for e in _leaves(inside):
            f = op_family(e.name)
            fam[f] = fam.get(f, 0.0) + e.dur_ns * 1e-9
            if f in ksum:
                ksum[f] += e.dur_ns * 1e-9
                kcalls[f] += 1
    n = len(devs)
    spans = host_spans(planes)

    def label(s: float, e: float) -> str:
        mid = (s + e) / 2
        inside = [h for h in spans if h.start_ns <= mid <= h.end_ns
                  and h.name != WINDOW_SPAN]
        return min(inside, key=lambda h: h.dur_ns).name if inside else (
            "no bench span")

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        programs=programs, kernels=ksum, kernel_calls=kcalls,
        device_ops=sorted(fam.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(label(s, e), (e - s) * 1e-9) for s, e in gaps[:top]])
