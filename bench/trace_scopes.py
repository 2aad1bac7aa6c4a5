#!/usr/bin/env python3
"""Split a profiler trace by the program's own names: device idle inside
each of the engine's ``serve.*`` host spans, and device time under each
named scope of the serving programs.

    python3 bench/trace_scopes.py --workload <cell> --seed <n> --seconds <s>

from the root of a checkout runs one traced run of the cell through the
harness, exactly as ``bench/run.py --trace 1`` does, prints its result
line with the split under ``"split"``, and two lines on standard error:
device idle per ``serve.*`` span (and outside ``serve.step``), and device
time per scope.

* ``serve.step`` is the span the engine opens around each
  ``ServeEngine.step`` call, ``serve.<phase>`` each host phase inside it;
* a device operation's scope is the first of ``SCOPES`` in its
  ``op_name`` path, which the trace's copy of the program's optimized HLO
  module gives each instruction (a fusion counts under its own
  ``op_name``); an op the module does not hold (a copy or convert the
  TPU compiler adds later) takes the ``op_name`` of its first operand
  that has one; ``other`` where none names a scope.

Device time and idle are counted as ``trace_reduce`` counts busy time:
inside the harness's traced window, averaged over the devices.  A
``serve.step`` span counts only when it lies wholly inside the window,
a phase span only inside a step that counts.  A trace of a
program without the spans or scopes splits into nothing: zero steps and
every operation under ``other``.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

if __package__ in (None, ""):
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import trace_reduce  # noqa: E402

STEP_SPAN = "serve.step"
SPAN_PREFIX = "serve."
SCOPES = ("attention", "ffn", "lm_head")
OTHER = "other"


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


# The device's op events name an HLO instruction but carry no ``op_name``;
# the trace keeps each program's optimized HLO module, as the ``Hlo Proto``
# stat of its event metadata in the ``/host:metadata`` plane.
# ``ProfileData`` does not expose those, so they are read from the
# protobuf wire format, by the field numbers of ``xplane.proto`` and
# ``hlo.proto``.

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one message: ints for varints, bytes
    for length-delimited fields; fixed-width fields are skipped."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} is not read here")
        yield key >> 3, v


def _first(buf, num: int):
    return next((v for f, v in _fields(buf) if f == num), None)


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    """HloProto.hlo_module → computations → instructions: name → the
    ``op_name`` of its OpMetadata."""
    out = {}
    module = _first(hlo_proto, 1)                     # hlo_module
    for f, comp in _fields(module or b""):
        if f != 3:                                    # computations
            continue
        for g, ins in _fields(comp):
            if g != 2:                                # instructions
                continue
            name = meta = None
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:                          # metadata
                    meta = _first(v, 2)               # op_name
            if name and meta is not None:
                out[name] = bytes(meta).decode()
    return out


def module_op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """Program name as the ``XLA Modules`` line gives it (``jit_f(7)``)
    → HLO instruction name → ``op_name``, for every program whose module
    the trace holds."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        stat_ids = set()
        for g, entry in _fields(plane):
            if g == 5:                                # stat_metadata map
                meta = _first(entry, 2)
                if meta is not None and _first(meta, 2) == b"Hlo Proto":
                    stat_ids.add(_first(meta, 1))
        for g, entry in _fields(plane):
            if g != 4:                                # event_metadata map
                continue
            meta = _first(entry, 2)
            name = bytes(_first(meta, 2) or b"").decode()
            for h, stat in _fields(meta):
                if h == 5 and _first(stat, 1) in stat_ids:   # stats
                    proto = _first(stat, 6)                    # bytes_value
                    if proto is not None:
                        out[name] = _instruction_op_names(proto)
    return out


_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def _operands(text: str) -> List[str]:
    """Operand names in an op event's HLO text: ``%a = f32[2] add(f32[2]
    %b, f32[2] %c)`` gives ``b``, ``c``; attribute references such as
    ``calls=%f`` are not operands."""
    _, sep, rhs = text.partition("=")
    return _OPERAND.findall(rhs) if sep else []


def _resolve(names: Dict[str, str], operands: Dict[str, List[str]],
             instr: str, depth: int = 4):
    """``instr``'s ``op_name``, else that of its first operand that has
    one, ``depth`` levels up: the TPU compiler adds copies and converts
    after the module the trace keeps was written, and such an op belongs
    to the layer whose value it moves."""
    if instr in names:
        return names[instr]
    if depth:
        for o in operands.get(instr, ()):
            got = _resolve(names, operands, o, depth - 1)
            if got is not None:
                return got
    return None


def scope_of(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return OTHER


@dataclasses.dataclass
class Split:
    steps: int                              # serve.step spans in window
    idle_s: Dict[str, float]                # span name -> device idle s
    outside_idle_s: float                   # device idle outside steps
    scope_s: Dict[str, float]               # scope -> device seconds
    program_scope_s: Dict[str, Dict[str, float]]  # program -> scope -> s
    unmapped_s: float          # device s of ops the trace's HLO does not name
    inferred_s: float          # device s named through an operand
    # longest idle gaps: innermost span, seconds, start in the window
    gaps: List[Tuple[str, float, float]]
    unmapped_ops: List[Tuple[str, float]]  # "program/op family" -> s

    @property
    def host_gap_ms(self):
        """Device idle inside ``serve.step`` per step, in ms."""
        if not self.steps:
            return None
        return self.idle_s.get(STEP_SPAN, 0.0) / self.steps * 1e3

    @property
    def attention_ms_per_step(self):
        """Device time under the ``attention`` scope per step, in ms."""
        if not self.steps:
            return None
        return self.scope_s.get("attention", 0.0) / self.steps * 1e3

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "host_gap_ms": self.host_gap_ms,
                "attention_ms_per_step": self.attention_ms_per_step}


class _Intervals:
    """Disjoint sorted intervals; total length inside any range."""

    def __init__(self, iv: List[Tuple[float, float]]):
        self.starts = [s for s, _ in iv]
        self.iv = iv
        self.cum = [0.0]
        for s, e in iv:
            self.cum.append(self.cum[-1] + e - s)

    def within(self, lo: float, hi: float) -> float:
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        j = bisect.bisect_left(self.starts, hi)
        if i >= j:
            return 0.0
        total = self.cum[j] - self.cum[i]
        s, e = self.iv[i]
        total -= max(0.0, min(e, lo) - s)        # part of the first
        s, e = self.iv[j - 1]
        total -= max(0.0, e - max(s, hi))        # part of the last
        return total


def _serve_spans(planes: trace_reduce.Planes) -> List[trace_reduce.Event]:
    return [e for name, lines in planes.items()
            if not name.startswith("/device:")
            for evs in lines.values() for e in evs
            if e.name.startswith(SPAN_PREFIX)]


def _module_of(mods: List[trace_reduce.Event], starts: List[float],
               t: float) -> str:
    """The ``XLA Modules`` event (program execution) running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i].name.strip() if i >= 0 and t < mods[i].end_ns else ""


def split(planes: trace_reduce.Planes,
          op_names: Dict[str, Dict[str, str]], top: int = 10) -> Split:
    """``op_names`` is ``module_op_names`` of the same trace; ``top`` the
    number of longest idle gaps kept, each named by the innermost
    ``serve.*`` span (of a counted step) that covers its midpoint."""
    lo, hi = trace_reduce.window(planes)
    devs = trace_reduce.device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane")
    spans = _serve_spans(planes)
    steps = sorted((e for e in spans if e.name == STEP_SPAN
                    and lo <= e.start_ns and e.end_ns <= hi),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in steps]

    def counted(e) -> bool:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        return i >= 0 and e.end_ns <= steps[i].end_ns
    spans = [e for e in spans if counted(e)]
    idle: Dict[str, float] = {}
    outside = unmapped = inferred = 0.0
    missed: Dict[str, float] = {}
    longest: List[Tuple[float, float]] = []
    scope: Dict[str, float] = {}
    program: Dict[str, Dict[str, float]] = {}
    for p in devs:
        lines = planes[p]
        ops = [e for e in trace_reduce._ops(lines)
               if e.end_ns > lo and e.start_ns < hi]
        busy = trace_reduce._union(trace_reduce._clip(
            [(e.start_ns, e.end_ns) for e in ops], lo, hi))
        gaps, prev = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        longest += gaps
        gaps = _Intervals(gaps)
        for h in spans:
            idle[h.name] = idle.get(h.name, 0.0) + gaps.within(
                h.start_ns, h.end_ns)
        outside += gaps.within(lo, hi) - sum(
            gaps.within(h.start_ns, h.end_ns) for h in steps)
        mods = sorted(lines.get("XLA Modules", []), key=lambda e: e.start_ns)
        mod_starts = [m.start_ns for m in mods]
        inside = [e for e in ops if lo <= e.start_ns and e.end_ns <= hi]
        operands: Dict[str, Dict[str, List[str]]] = {}
        for e in inside:
            operands.setdefault(_module_of(mods, mod_starts, e.start_ns),
                                {})[trace_reduce.op_name(e.name)] = \
                _operands(e.name)
        for e in trace_reduce._leaves(inside):
            mod = _module_of(mods, mod_starts, e.start_ns)
            instr = trace_reduce.op_name(e.name)
            names = op_names.get(mod, {})
            named = _resolve(names, operands.get(mod, {}), instr)
            if named is not None and instr not in names:
                inferred += e.dur_ns
            if named is None:
                unmapped += e.dur_ns
                key = (f"{trace_reduce.program_name(mod) if mod else OTHER}"
                       f"/{trace_reduce.op_family(e.name)}"
                       + ("" if mod in op_names else " (no module)"))
                missed[key] = missed.get(key, 0.0) + e.dur_ns
            sc = scope_of(named or "")
            scope[sc] = scope.get(sc, 0.0) + e.dur_ns
            per = program.setdefault(
                trace_reduce.program_name(mod) if mod else OTHER, {})
            per[sc] = per.get(sc, 0.0) + e.dur_ns
    n = len(devs) * 1e9

    def label(g: Tuple[float, float]) -> str:
        mid = (g[0] + g[1]) / 2
        inner = [h for h in spans if h.start_ns <= mid <= h.end_ns]
        return min(inner, key=lambda h: h.dur_ns).name if inner else (
            "no serve span")
    longest = sorted(longest, key=lambda g: g[0] - g[1])[:top]
    return Split(
        steps=len(steps),
        idle_s={k: v / n for k, v in sorted(idle.items())},
        outside_idle_s=outside / n,
        scope_s={k: v / n for k, v in sorted(scope.items())},
        program_scope_s={p: {k: v / n for k, v in sorted(d.items())}
                         for p, d in sorted(program.items())},
        unmapped_s=unmapped / n, inferred_s=inferred / n,
        gaps=[(label(g), (g[1] - g[0]) * 1e-9, (g[0] - lo) * 1e-9)
              for g in longest],
        unmapped_ops=[(k, v / n) for k, v in sorted(
            missed.items(), key=lambda kv: -kv[1])[:top]])


def describe(s: Split) -> List[str]:
    """The two stderr lines: idle per span, device time per scope."""
    idle = ", ".join(f"{k} {v * 1e3:.1f} ms" for k, v in s.idle_s.items())
    scopes = "; ".join(
        f"{p}: " + ", ".join(f"{k} {v:.3f} s" for k, v in d.items())
        for p, d in s.program_scope_s.items())
    gaps = ", ".join(f"{k} {v * 1e3:.1f} at {t:.1f} s"
                     for k, v, t in s.gaps)
    return [f"device idle by span over {s.steps} serve.step spans: "
            f"{idle or 'no serve span'}; outside serve.step "
            f"{s.outside_idle_s * 1e3:.1f} ms; host_gap_ms "
            f"{s.host_gap_ms}; longest gaps (ms) {gaps}",
            f"device time by scope: {scopes}; named through an operand "
            f"{s.inferred_s:.3f} s, not named {s.unmapped_s:.3f} s "
            f"{s.unmapped_ops}; attention_ms_per_step "
            f"{s.attention_ms_per_step}"]


@contextlib.contextmanager
def splitting(out: dict):
    """While open, every trace the harness loads is also split into
    ``out``.  The harness deletes its trace before
    it returns, so this swaps ``trace_reduce.load`` for the run until the
    harness keeps the split itself (PERF.md §7)."""
    original = trace_reduce.load

    def load_and_split(trace_dir):
        planes = original(trace_dir)
        out.update(split(planes, module_op_names(
            newest_xplane(trace_dir))).as_dict())
        return planes
    trace_reduce.load = load_and_split
    try:
        yield out
    finally:
        trace_reduce.load = original


def main() -> int:
    import argparse
    import json
    import sys
    import time
    t_start = time.perf_counter()
    from bench import entry
    entry.prepare()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    try:
        with splitting({}) as got:
            out = harness.run(cell, args.seed, args.seconds, True, t_start)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    s = Split(**{f.name: got[f.name] for f in dataclasses.fields(Split)})
    for line in describe(s):
        print(line, file=sys.stderr, flush=True)
    out["split"] = s.as_dict()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
