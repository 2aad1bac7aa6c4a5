#!/usr/bin/env python3
"""Smoke run of packed serving on a TPU at olmo-1b's full published width.

One smoke run, not a benchmark: it shows that the serving engine's main
path starts on the chip and gives correct results there.  In one process
it

1. builds olmo-1b at its published widths (16 layers, d_model 2048, d_ff
   8192, vocab 50304; random weights from ``--seed``), pruned to 75 %
   weight sparsity, on one device held to ``jax.devices()[:1]``;
2. checks correctness: one batch of prompts goes through a dense-dispatch
   engine (``stream_weights=False``) and through the packed engine over the
   same pruned weights, and their first-step logits must agree within
   ``LOGIT_RTOL``;
3. serves a seeded trace (8 requests over 4 slots, ``max_len`` 256, paged KV
   cache, chunked prefill) through the compiled ``bitmap_spmm`` kernel and
   prints the device kind, the pack manifest, the count of
   ``tpu_custom_call`` kernels in the compiled decode step, compile time,
   tokens, tok/s and time to first token.

``--chips 4`` runs only the multi-chip phase: the packed deployment at
``model_parallel=4`` over four chips against ``model_parallel=1`` on one,
on the same trace.  First-step logits must agree within ``LOGIT_RTOL``;
how many greedy tokens match is reported beside them.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Where JAX finds no TPU, or any phase fails, the script exits nonzero and
prints no such line.

Run from the checkout root:  python chip_smoke.py [--chips 4] [--seed N]
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time
import traceback

#: First-step logits of two dispatch paths over the same weights must agree
#: to this relative L2 error.  Activations are bf16: each of the ~100
#: matmul outputs on the path of one token through 16 layers rounds to a
#: unit roundoff of 2**-9 (about 2e-3), and the two paths (XLA's dot and the
#: kernel's tile-wise f32 accumulation) round differently; independent
#: rounding errors add to about sqrt(100) * 2e-3 = 2e-2.  A wrong expansion
#: of the compressed weights gives an error of order 1.
LOGIT_RTOL = 2e-2

ARCH = "olmo-1b"
SPARSITY = 0.75
SLOTS = 4
MAX_LEN = 256
PAGE_LEN = 16
PREFILL_CHUNK = 32
REQUESTS = 8
PROMPT_LEN = (16, 96)
MAX_NEW = (16, 48)


def _say(msg: str) -> None:
    print(msg, flush=True)


def _rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _engine(devices, *, stream: bool, model_parallel: int = 1, seed: int):
    from repro.serve import ServeEngine
    return ServeEngine.from_arch(
        ARCH, smoke=False, num_slots=SLOTS, max_len=MAX_LEN,
        sparsity=SPARSITY, head_sparsity=0.0, seed=seed,
        model_parallel=model_parallel, devices=devices,
        stream_weights=stream, bitmap_head=stream, paged=True,
        page_len=PAGE_LEN, prefill_chunk=PREFILL_CHUNK)


def _probe_tokens(vocab: int, seed: int):
    import numpy as np
    return np.random.default_rng(seed).integers(0, vocab, SLOTS)


def _serve(eng, seed: int):
    """Drive the seeded trace; returns (report, {rid: tokens})."""
    from repro.launch.serve import make_trace, run_trace
    trace = make_trace(eng, REQUESTS, rate=0.5, seed=seed,
                       prompt_len=PROMPT_LEN, max_new=MAX_NEW)
    rep, shed = run_trace(eng, trace)
    if shed or rep["requests"] != REQUESTS:
        raise RuntimeError(f"{rep['requests']} of {REQUESTS} requests "
                           f"completed ({shed} shed)")
    return rep, {r.rid: list(r.tokens) for r in eng.requests}


def _manifest(eng) -> None:
    pm = eng.packed
    reasons = collections.Counter(e.reason for e in pm.fallback_entries)
    _say(f"manifest: {len(pm.packed_entries)} tensors packed, "
         f"{len(pm.fallback_entries)} dense fallbacks")
    for reason, n in sorted(reasons.items()):
        _say(f"  fallback x{n}: {reason}")
    head = ("packed" if eng.lm_weight is not None
            else f"dense ({eng.head_fallback})")
    _say(f"  LM head: {head}; engine fallbacks: {eng.fallbacks or 'none'}")
    ws = eng.weight_stream_report()
    _say(f"  modeled weight stream per decode step: "
         f"{ws['sparse_bytes_per_step'] / 1e6:.1f} MB packed vs "
         f"{ws['dense_bytes_per_step'] / 1e6:.1f} MB dense "
         f"({ws['reduction']:.2f}x)")


def phase_one_chip(seed: int) -> None:
    import jax
    from repro.kernels.ops import default_impl
    devs = jax.devices()[:1]

    t0 = time.perf_counter()
    dense = _engine(devs, stream=False, seed=seed)
    tokens = _probe_tokens(dense.cfg.vocab_size, seed)
    ref = dense.probe_logits(tokens)
    _say(f"dense-dispatch engine: built and probed in "
         f"{time.perf_counter() - t0:.1f}s "
         f"(weight sparsity {dense.weight_sparsity:.4f})")
    del dense          # free its device buffers before the next engine
    gc.collect()

    t0 = time.perf_counter()
    eng = _engine(devs, stream=True, seed=seed)
    _say(f"packed engine: built (prune + pack) in "
         f"{time.perf_counter() - t0:.1f}s; kernel dispatch "
         f"{default_impl()!r}")
    if default_impl() != "pallas":
        raise RuntimeError("the kernels do not dispatch compiled Pallas")
    _manifest(eng)
    t0 = time.perf_counter()
    eng.warmup()
    _say(f"compile (decode + prefill steps): "
         f"{time.perf_counter() - t0:.1f}s")

    got = eng.probe_logits(tokens)
    err = _rel_l2(got, ref)
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    _say(f"logit check: packed vs dense first-step logits rel L2 "
         f"{err:.3e} (limit {LOGIT_RTOL:.0e}), argmax agreement "
         f"{agree:.2f}, finite {bool((got == got).all())}")
    if not err <= LOGIT_RTOL:
        raise RuntimeError(f"packed logits disagree with dense: {err:.3e}")

    calls = {phase: eng.lower(phase).compile().as_text().count(
                 'custom_call_target="tpu_custom_call"')
             for phase in ("decode", "prefill")}
    _say(f"compiled decode step: {calls['decode']} tpu_custom_call "
         f"kernels; prefill step: {calls['prefill']}")
    if calls["decode"] == 0:
        raise RuntimeError("decode step holds no compiled bitmap_spmm")

    rep, _ = _serve(eng, seed)
    ftl = rep["first_token_s"]
    _say(f"served (one smoke run, not a benchmark): {rep['requests']} "
         f"requests, {rep['generated_tokens']} tokens in "
         f"{rep['wall_s']:.2f}s = {rep['tok_per_s']:.1f} tok/s; TTFT p50 "
         f"{ftl['p50'] * 1e3:.1f} ms; slot occupancy "
         f"{rep['slot_occupancy']:.2f}")


def phase_four_chips(seed: int) -> None:
    import jax
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devs)}")
    out = {}
    for mp, d in ((1, devs[:1]), (4, devs[:4])):
        t0 = time.perf_counter()
        eng = _engine(d, stream=True, model_parallel=mp, seed=seed)
        logits = eng.probe_logits(_probe_tokens(eng.cfg.vocab_size, seed))
        rep, toks = _serve(eng, seed)
        out[mp] = (logits, toks)
        _say(f"model_parallel={mp} on {len(d)} chip(s): mesh "
             f"{dict(eng.mesh.shape)}, {rep['generated_tokens']} tokens, "
             f"{rep['tok_per_s']:.1f} tok/s (one smoke run), "
             f"{time.perf_counter() - t0:.1f}s with set-up")
        del eng
        gc.collect()
    err = _rel_l2(out[4][0], out[1][0])
    pairs = [(a, b) for rid in out[1][1]
             for a, b in zip(out[1][1][rid], out[4][1][rid])]
    same = sum(a == b for a, b in pairs)
    _say(f"4-chip vs 1-chip: first-step logits rel L2 {err:.3e} (limit "
         f"{LOGIT_RTOL:.0e}); greedy tokens equal {same}/{len(pairs)}, "
         f"every request identical: {out[4][1] == out[1][1]}")
    if not err <= LOGIT_RTOL:
        raise RuntimeError("model_parallel=4 disagrees with one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    try:
        import jax
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the serving stack: {e}",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing "
              f"served", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    hits = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **_: hits.update([name.rsplit("/", 1)[-1]]))
    _say(f"device: {dev.device_kind} ({dev.platform}), "
         f"{len(jax.devices())} visible; compile cache {cache}")

    phase = phase_four_chips if args.chips == 4 else phase_one_chip
    try:
        phase(args.seed)
    except Exception:
        traceback.print_exc()
        print(f"chip_smoke: phase {phase.__name__} failed", file=sys.stderr)
        return 1
    _say(f"compile cache: {hits['cache_hits']} hits, "
         f"{hits['cache_misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
