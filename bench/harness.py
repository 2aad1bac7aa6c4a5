"""One run of one cell: set-up, measured window, correctness and
metrics.

The timed entry is the program's packed ``ServeEngine``: the harness
keeps the engine's queue topped up (each request submitted with
``arrival`` equal to the engine's current step, so the engine treats it
as due at once), calls ``step()`` in a loop, and stamps every new token
with the host clock after the step that produced it.  Nothing is taken
from the engine's own step-indexed latency accounting.

The harness wraps the engine instance's own decode and prefill calls
(``Calls``): it counts the live rows of each call, for the kernels'
least time, and keeps on the device a fixed slice of every decode call's
logits, for the comparison with the reference after the window.

Set-up (``setup_s``) runs from the process start until the window opens:
the weights made on the device from the seed, the engine built around
them (its prune and host pack), the cell's decode and prefill programs
warmed from the persistent compile cache, the heap collected and
frozen, and the fill.  The reference check runs after the window, once
the engine's state is freed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from bench import compare, counters, generator, peaks, spec, trace_reduce
from bench import weights as bench_weights

KERNELS = ("bitmap_spmm",)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Tracked:
    """The harness's own view of one request."""
    plan: generator.Planned
    req: object = None              # the engine's Request
    admitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    seen: int = 0
    processed: int = 0              # positions run through the model
    failed: Optional[str] = None
    done: bool = False
    # (decode call, slot) that produced each served token
    rows: List[Tuple[int, int]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    """What the host saw between the window's open and close."""
    t_open: float = 0.0             # 0 until the window opens
    t_close: float = 0.0
    tokens: int = 0
    gaps: List[float] = dataclasses.field(default_factory=list)
    occupancy: List[float] = dataclasses.field(default_factory=list)
    steps: int = 0
    flops: float = 0.0
    compiles: int = 0
    gc_s: List[float] = dataclasses.field(default_factory=list)
    step_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def open(self, t: float, seconds: float) -> None:
        self.t_open, self.t_close = t, t + seconds

    def inside(self, t: float) -> bool:
        return 0.0 < self.t_open <= t <= self.t_close


@contextlib.contextmanager
def served_weights(params_box: list):
    """Hand the benchmark's pruned weights to the engine as its initial
    parameters (the engine then prunes them again, which changes nothing,
    and packs them).  The box is emptied so that the engine holds the
    only reference."""
    import jax
    from repro.models.model import param_shapes
    import repro.serve.engine as engine_mod

    original = engine_mod.init_params

    def init(_rng, cfg):
        params = params_box.pop()
        got = jax.tree.map(lambda a: tuple(a.shape), params)
        if got != param_shapes(cfg):
            raise ValueError("bench weights do not match the program's "
                             "parameter layout")
        return params

    engine_mod.init_params = init
    try:
        yield
    finally:
        engine_mod.init_params = original


class Calls:
    """Wraps the engine instance's ``_decode`` and ``_prefill``.

    While ``counting``, each call's live rows go to ``decode_rows`` and
    ``prefill_rows``: the slots that decode (active and past their
    prompt), and the prompt positions the prefill call writes.  While
    ``capturing``, each decode call's logits at the vocabulary columns
    ``cols`` and their row maximum are kept on the device, one
    (num_slots, len(cols) + 1) block per call, and ``slots`` maps each
    decoding request to its row."""

    def __init__(self, eng, cols):
        import jax
        import jax.numpy as jnp
        self.eng, self.cols = eng, jnp.asarray(cols, jnp.int32)
        self.counting = self.capturing = False
        self.decode_rows: List[int] = []
        self.prefill_rows: List[int] = []
        self.kept: list = []
        self.slots: Dict[int, int] = {}

        @jax.jit
        def keep(logits, cols):
            lg = logits.astype(jnp.float32)
            return jnp.concatenate([lg[:, cols], lg.max(-1, keepdims=True)],
                                   -1)
        self._keep = keep
        self._decode, self._prefill = eng._decode, eng._prefill
        eng._decode, eng._prefill = self.decode, self.prefill

    def decode(self, tok, pos):
        eng = self.eng
        planner = eng.planner
        live = {id(r): s for s, r in eng.scheduler.active.items()
                if planner is None or not planner.in_prefill(s)}
        nxt, logits, cache = self._decode(tok, pos)
        if self.counting:
            self.decode_rows.append(len(live))
        if self.capturing:
            self.kept.append(self._keep(logits, self.cols))
            self.slots = live
        return nxt, logits, cache

    def prefill(self, tokens, pos, lens):
        if self.counting:
            self.prefill_rows.append(int(sum(int(n) for n in lens)))
        return self._prefill(tokens, pos, lens)

    def release(self) -> None:
        """Give the engine its own calls back and drop it."""
        self.eng._decode, self.eng._prefill = self._decode, self._prefill
        self.eng = self._decode = self._prefill = None

    def rows_of(self, index: List[Tuple[int, int]]):
        """Host (T, len(cols) + 1) array of the kept rows ``index``."""
        import jax.numpy as jnp
        import numpy as np
        calls = sorted({c for c, _ in index})
        at = {c: i for i, c in enumerate(calls)}
        block = np.asarray(jnp.stack([self.kept[c] for c in calls]))
        return np.stack([block[at[c], s] for c, s in index])


def _reader(name: str):
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


class Run:
    """State of one run; the metric readers take it as their context."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 trace: bool, t_start: float, require_tpu: bool = True,
                 control: bool = False, log=sys.stderr):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.t_start = bool(trace), t_start
        self.require_tpu, self.control, self.log = require_tpu, control, log
        self.model = cell.config["model"]
        self.sparsity = float(cell.config["engine"]["sparsity"])
        self.win = Window()
        self.tracked: List[Tracked] = []
        self.split: Dict[str, float] = {}
        self.reduction: Optional[trace_reduce.Reduction] = None
        self.weight_stream: dict = {}
        self.kernel_tensors: set = set()
        self.peak: dict = {}
        self.device: dict = {}
        self.setup_s = 0.0
        self.checks: Dict[str, Dict[str, float]] = {}
        self.readings: Dict[str, Dict[str, float]] = {}
        self.cache: Dict[str, int] = {}
        self.calls: Optional[Calls] = None
        self.cols = None
        self.heap: Dict[str, float] = {}

    def say(self, msg: str) -> None:
        print(msg, file=self.log, flush=True)

    # ------------------------------------------------------------ set-up --
    def _devices(self):
        import jax
        devs = jax.devices()
        if self.require_tpu and (devs[0].platform != "tpu"
                                 or len(devs) < self.cell.chips):
            raise NoChip(f"cell {self.cell.name} needs {self.cell.chips} "
                         f"TPU chip(s); JAX found {len(devs)} "
                         f"{devs[0].platform!r} device(s)")
        return devs[:self.cell.chips]

    def _compile_listener(self):
        import jax

        def on_event(name):
            if name.endswith(("backend_compile_duration", "cache_hits")) \
                    and self.win.inside(time.perf_counter()):
                self.win.compiles += 1
            for k in ("cache_hits", "cache_misses"):
                if name.endswith(k):
                    self.cache[k] = self.cache.get(k, 0) + 1
        jax.monitoring.register_event_listener(
            lambda name, **kw: on_event(name))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, dur, **kw: on_event(name))

    def build(self):
        import jax
        from repro.serve import ServeEngine
        devs = self._devices()
        dev = devs[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs)}
        if self.require_tpu:
            self.peak = peaks.peaks(dev.device_kind)
        self._compile_listener()
        t = time.perf_counter()
        box = [bench_weights.init(self.cell.config, self.seed)]
        jax.block_until_ready(box[0])
        self.split["init_s"] = time.perf_counter() - t
        t = time.perf_counter()
        box[0] = bench_weights.prune(box[0], self.sparsity)
        jax.block_until_ready(box[0])
        self.split["prune_s"] = time.perf_counter() - t
        t = time.perf_counter()
        with served_weights(box):
            eng = ServeEngine(
                spec.model_config(self.cell.config), devices=devs,
                seed=int(generator.rng_for(self.seed, "engine").integers(
                    2 ** 31)), **self.cell.engine_kwargs)
        self.split["engine_s"] = time.perf_counter() - t
        t = time.perf_counter()
        eng.warmup()
        # the admit path seeds each slot's sampling key: compile it here
        jax.block_until_ready(jax.random.PRNGKey(1))
        self.split["compile_s"] = time.perf_counter() - t
        self.weight_stream = eng.weight_stream_report()
        pm = eng.packed
        self.kernel_tensors = {tuple(e.path.split("/")[-2:])
                               for e in pm.packed_entries}
        if eng.lm_weight is not None:
            self.kernel_tensors.add(("lm", "head"))
        self.say(f"engine: packed {sorted(self.kernel_tensors)}; "
                 f"{len(pm.fallback_entries)} dense "
                 f"({sorted({e.reason for e in pm.fallback_entries})}); "
                 f"fallbacks {eng.fallbacks or 'none'}")
        return eng

    def settle(self) -> None:
        """Collect set-up's garbage and freeze what is left, so that no
        collection inside the window walks the objects set-up made (a
        long-running server does the same once it has loaded); then time
        every collection from here on."""
        t = time.perf_counter()
        gc.collect()
        gc.freeze()
        self.heap = {"collect_s": time.perf_counter() - t,
                     "frozen": gc.get_freeze_count()}
        started = []

        def on_gc(phase, info):
            if phase == "start":
                started[:] = [time.perf_counter()]
            elif started and self.win.inside(started[0]):
                self.win.gc_s.append(time.perf_counter() - started[0])
        gc.callbacks.append(on_gc)
        self._on_gc = on_gc

    def unsettle(self) -> None:
        if getattr(self, "_on_gc", None) in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        gc.unfreeze()

    # -------------------------------------------------------------- loop --
    def _submit(self, eng, tr: Tracked) -> None:
        from repro.serve.errors import ServeError
        import jax
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                tr.req = eng.submit(tr.plan.prompt, tr.plan.max_new,
                                    arrival=float(eng._steps))
        except (ServeError, ValueError) as e:
            tr.failed = f"refused: {e}"
        self.tracked.append(tr)

    def _observe(self, eng, t: float, live: List[Tracked]) -> List[Tracked]:
        """Stamp what the step just produced; returns the still-live."""
        from repro.serve.request import RequestState
        win = self.win
        in_win = win.inside(t)
        planner = eng.planner
        still = []
        for tr in live:
            r = tr.req
            if tr.admitted is None and r.slot is not None:
                tr.admitted = t
            n = len(r.tokens)
            if n > tr.seen and self.calls.capturing:
                # one decode call per step makes at most one token
                if n - tr.seen != 1 or id(r) not in self.calls.slots:
                    raise RuntimeError("a served token the harness cannot "
                                       "place in a decode call")
                tr.rows.append((len(self.calls.kept) - 1,
                                self.calls.slots[id(r)]))
            if n > tr.seen:
                if tr.first is None:
                    tr.first = t
                elif in_win and tr.last >= win.t_open:
                    win.gaps.append(t - tr.last)
                    win.gaps.extend([0.0] * (n - tr.seen - 1))
                if in_win:
                    win.tokens += n - tr.seen
                tr.last, tr.seen = t, n
            # positions run through the model so far: the prompt's first
            # L - 1 by prefill, then one per decode step
            L = len(tr.plan.prompt)
            if n:
                done_pos = L - 1 + n
            elif r.state is RequestState.ACTIVE:
                done_pos = (planner.next_pos(r.slot)
                            if planner is not None
                            and planner.in_prefill(r.slot) else L - 1)
            else:
                done_pos = tr.processed
            if in_win and done_pos > tr.processed:
                # prompt positions before the last go through prefill,
                # which stops before the LM head; the rest through decode
                p0, p1 = tr.processed, done_pos
                q = min(max(p0, L - 1), p1)
                win.flops += counters.positions_flops(
                    self._body_flops, self._ctx_flops, p0, q)
                win.flops += counters.positions_flops(
                    self._tok_flops, self._ctx_flops, q, p1)
            tr.processed = max(tr.processed, done_pos)
            if r.state in (RequestState.WAITING, RequestState.ACTIVE):
                still.append(tr)
            elif r.state is RequestState.DONE:
                tr.done = True
            else:
                tr.failed = f"{r.state.name.lower()}: {r.error}"
        if in_win:
            win.steps += 1
            win.occupancy.append(len(eng.scheduler.active) / eng.num_slots)
        return still

    def _step(self, eng, live: List[Tracked]) -> List[Tracked]:
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        now = time.perf_counter()
        if self.win.inside(now):
            self.win.step_s.append(now - t)
        return self._observe(eng, now, live)

    def drive(self, eng) -> None:
        """Fill, then the measured window."""
        import jax
        traffic = generator.Traffic(self.cell.traffic, self.seed,
                                    self.model["vocab_size"])
        self._tok_flops = counters.token_flops(self.model, self.sparsity)
        self._body_flops = counters.token_flops(self.model, self.sparsity,
                                                head=False)
        self._ctx_flops = counters.attn_flops_per_ctx(self.model)
        win = self.win
        live: List[Tracked] = []
        source = traffic.backlog()
        qmin = int(self.cell.traffic["queue_min"])

        def top_up():
            while len(eng.scheduler.waiting) < qmin:
                tr = Tracked(next(source))
                self._submit(eng, tr)
                if tr.failed is None:
                    live.append(tr)
        top_up()
        planner = eng.planner

        def filling():
            return len(eng.scheduler.active) < eng.num_slots or (
                planner is not None and any(
                    planner.in_prefill(s) for s in eng.scheduler.active))
        self.calls.capturing = True
        while filling():
            live = self._step(eng, live)
            top_up()
        self.setup_s = time.perf_counter() - self.t_start
        with self._traced():
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                self.calls.counting = True
                win.open(time.perf_counter(), self.seconds)
                while time.perf_counter() < win.t_close:
                    live = self._step(eng, live)
                    top_up()
                self.calls.counting = False
        self.calls.capturing = False

    @contextlib.contextmanager
    def _traced(self):
        if not self.trace:
            yield
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    # ------------------------------------------------------------ results --
    def window_requests(self) -> List[Tracked]:
        """Requests the window is answerable for: those in flight during
        it."""
        w = self.win
        return [t for t in self.tracked
                if t.admitted is not None and t.admitted <= w.t_close
                and not (t.done and t.last < w.t_open)]

    def failed(self) -> List[Tracked]:
        """Refused, shed, expired or errored."""
        return [t for t in self.window_requests() if t.failed is not None]

    def correctness(self, params_fn) -> None:
        """Compare a sample of the finished requests with the reference:
        the program's readings, and the control's with ``control``.  The
        checks are the readings that the cell's limits file gives a limit,
        the control's in a control run, so that it reads as the program
        would."""
        finished = [t for t in self.tracked if t.done and t.req.tokens]
        for t in finished:
            if len(t.rows) != len(t.req.tokens):
                raise RuntimeError(f"request {t.plan.idx}: "
                                   f"{len(t.req.tokens)} tokens served, "
                                   f"{len(t.rows)} decode rows kept")
        pairs = [(t.plan.prompt, list(t.req.tokens)) for t in finished]
        s = self.cell.traffic["sample"]
        pick = compare.sample(pairs, self.seed, int(s["min_tokens"]),
                              int(s["max_requests"]))
        limits = spec.limits_of(self.cell)
        if not pick:
            self.checks = {k: {"value": float("inf"), "limit": v}
                           for k, v in limits.items()}
            self.say("check: no finished request to compare")
            return
        items = [(*pairs[i], self.calls.rows_of(finished[i].rows))
                 for i in pick]
        self.calls.kept.clear()
        params = params_fn()
        t = time.perf_counter()
        out = compare.check(params, self.model, items,
                            int(self.cell.engine_kwargs["max_len"]),
                            self.cols, control=self.control)
        del params
        self.split["reference_s"] = time.perf_counter() - t
        self.say(f"reference: {len(pick)} requests, {out['tokens']} served "
                 f"tokens compared in {self.split['reference_s']:.1f}s")
        self.readings = {k: out[k] for k in ("program", "control")
                         if k in out}
        for who, vals in self.readings.items():
            self.say(f"{who}: " + ", ".join(f"{k} {v!r}"
                                            for k, v in vals.items()))
        mine = self.readings["control" if self.control else "program"]
        self.checks = {k: {"value": mine[k], "limit": v}
                       for k, v in limits.items()}

    def metrics(self) -> Dict[str, dict]:
        out = {}
        for m in self.cell.metrics(self.trace):
            v = _reader(m["name"])(self)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True, control: bool = False,
        log=sys.stderr, engine_hook=None) -> dict:
    """One run; returns the result line's object.  ``engine_hook`` lets a
    test break the built engine underneath the harness.  With
    ``control`` the reference in float8 takes the program's place in the
    checks, and ``readings`` holds both the program's and the control's
    numbers."""
    import jax
    r = Run(cell, seed, seconds, trace, t_start, require_tpu, control, log)
    eng = r.build()
    if engine_hook is not None:
        engine_hook(eng)
    r.cols = compare.columns(seed, r.model["vocab_size"],
                             int(cell.traffic["sample"]["columns"]))
    r.calls = Calls(eng, r.cols)
    r.settle()
    r.say("set-up split: " + ", ".join(f"{k} {v:.2f}"
                                        for k, v in r.split.items())
          + f"; compile cache {r.cache}; heap: collected in "
          f"{r.heap['collect_s']:.3f}s, {r.heap['frozen']} objects frozen")
    try:
        r.drive(eng)
    finally:
        r.unsettle()
    w = r.win
    r.say(f"window: {w.seconds:.2f}s, {w.steps} steps, {w.tokens} tokens, "
          f"{len(w.gaps)} gaps, compiles in window {w.compiles}; "
          f"set-up {r.setup_s:.2f}s; {len(w.gc_s)} collections in the "
          f"window, longest {max(w.gc_s, default=0.0) * 1e3:.1f} ms; "
          f"slowest steps (s) "
          f"{[round(x, 3) for x in sorted(w.step_s)[-4:]]}")
    stats = jax.devices()[0].memory_stats() or {}
    r.device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    attempted = len(r.window_requests())
    failed = len(r.failed())
    r.calls.release()
    del eng
    gc.collect()
    if r.trace:
        r.reduction = trace_reduce.reduce(
            trace_reduce.load(r._trace_dir), kernels=KERNELS)
        shutil.rmtree(r._trace_dir, ignore_errors=True)
        r.device["busy_s"] = r.reduction.busy_s
        r.device["window_s"] = r.reduction.window_s
        n = {p: len(r.reduction.programs.get(p, []))
             for p in ("serve_step", "prefill_step")}
        r.say(f"trace: {n['serve_step']} decode and {n['prefill_step']} "
              f"prefill executions; the harness counted "
              f"{len(r.calls.decode_rows)} and {len(r.calls.prefill_rows)}")
    r.correctness(lambda: bench_weights.make(cell.config, seed))
    correct = all(c["value"] <= c["limit"] for c in r.checks.values())
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": r.metrics(), "device": r.device}
    if r.trace:
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in r.reduction.device_ops],
            "idle_gaps": [[n, s] for n, s in r.reduction.idle_gaps]}
    if control:
        out["readings"] = r.readings
    out["checks"] = r.checks
    for k, c in r.checks.items():
        r.say(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return out
