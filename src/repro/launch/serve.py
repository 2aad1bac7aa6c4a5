"""Serving CLI: thin driver over the continuous-batching engine.

The old straight-line decode loop now lives in ``repro.serve.ServeEngine``:
a request queue + slot scheduler + slotted KV cache keep decode running at
full batch width under staggered arrivals, with the model's L1-pruned
weights and the LM head streamed in the paper's bitmap-compressed format
through the ``kernels/ops`` dispatch (see DESIGN.md / EXPERIMENTS.md §Perf).

Run (CPU example, staggered Poisson arrivals):
  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
      --sparsity 0.5
"""
from __future__ import annotations

import argparse
from typing import List, Sequence, Tuple

import jax
import numpy as np

from repro.launch import compile_cache
from repro.serve import FaultPlan, ServeEngine, ServeOverloaded, \
    poisson_trace


def serve(arch: str, smoke: bool = True, batch: int = 4, steps: int = 32,
          max_len: int = 128, sparsity: float = 0.0, seed: int = 0,
          model_parallel: int = 1) -> dict:
    """Lock-step compatibility wrapper: ``batch`` simultaneous requests,
    each decoding ``steps`` tokens — the old serve() contract, now routed
    through the engine (returns the (batch, steps) greedy token matrix).

    ``head_sparsity=0.0`` keeps the old contract's *numerics*: the whole
    stack (and the head) streams through the bitmap path but packing is
    lossless, so for token-frontend archs the greedy tokens match the
    pre-engine straight-line loop (which served dense) exactly.  Frames-
    frontend archs (musicgen) derive their per-step embeds from a jax
    PRNG key folded with the step counter — same distribution, different
    sequence than the old host-RNG loop.
    """
    eng = ServeEngine.from_arch(arch, smoke=smoke, num_slots=batch,
                                max_len=max_len, sparsity=sparsity,
                                seed=seed, model_parallel=model_parallel,
                                head_sparsity=0.0)
    if sparsity > 0:
        print(f"serving at {eng.weight_sparsity:.2%} weight sparsity "
              f"(head compression {eng.head_compression:.2f}x)")
    rng = np.random.default_rng(seed)
    first = rng.integers(0, eng.cfg.vocab_size, (batch, 1))
    with jax.set_mesh(eng.mesh):
        reqs = [eng.submit([int(first[b, 0])], max_new_tokens=steps)
                for b in range(batch)]
        rep = eng.run()
    tokens = np.stack([np.asarray(r.tokens, np.int32) for r in reqs])
    print(f"decoded {steps} steps x batch {batch} in {rep['wall_s']:.2f}s "
          f"({rep['tok_per_s']:.1f} tok/s)")
    return {"tokens": tokens, "tok_per_s": rep["tok_per_s"],
            "report": rep}


def make_trace(eng: ServeEngine, requests: int, rate: float, seed: int,
               prompt_len: Tuple[int, int] = (1, 4),
               max_new: Tuple[int, int] = (8, 24)) -> List[dict]:
    """The seeded Poisson trace ``serve_trace`` drives, clipped so every
    request fits the engine's ``max_len``."""
    plen = (min(prompt_len[0], eng.max_len), min(prompt_len[1], eng.max_len))
    hi = max(1, min(max_new[1], eng.max_len - plen[1] + 1))
    lo = max(1, min(max_new[0], hi))
    return poisson_trace(requests, rate=rate, seed=seed,
                         vocab_size=eng.cfg.vocab_size, prompt_len=plen,
                         max_new=(lo, hi))


def run_trace(eng: ServeEngine, trace: Sequence[dict],
              temperature: float = 0.0) -> Tuple[dict, int]:
    """Submit ``trace`` under the engine's mesh and drain it; returns the
    engine report and the count of arrivals shed at submit."""
    shed_at_submit = 0
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            try:
                eng.submit(**spec, temperature=temperature)
            except ServeOverloaded:
                # admission control said no — the typed rejection is the
                # feature, not a failure; count it and keep the trace going
                shed_at_submit += 1
        rep = eng.run()
    return rep, shed_at_submit


def serve_trace(arch: str, smoke: bool = True, slots: int = 4,
                requests: int = 8, rate: float = 0.5, max_len: int = 128,
                max_new: tuple = (8, 24), sparsity: float = 0.0,
                head_sparsity: float | None = None, seed: int = 0,
                model_parallel: int = 1, devices=None,
                stream_weights: bool = True,
                temperature: float = 0.0, top_k: int = 0,
                paged: bool = False, page_len: int = 16,
                page_pool_tokens: int | None = None,
                prefill_chunk: int = 0, prefix_reuse: bool = False,
                preempt: bool = False,
                deadline_ms: float | None = None,
                max_queue: int | None = None,
                ttft_budget_ms: float | None = None,
                max_preempts: int = 8, audit: bool = False,
                faults: "FaultPlan | None" = None,
                trace_out: str | None = None,
                events_out: str | None = None,
                metrics_out: str | None = None,
                verbose: bool = True) -> dict:
    """Continuous-batching mode: seeded Poisson arrivals into the engine.

    ``devices`` (default: all) are the devices the engine's mesh spans.
    ``head_sparsity`` defaults to ``sparsity`` (the serving regime: the
    LM head is per-tensor pruned before bitmap packing); pass 0.0 to
    stream the exact dense head.  ``stream_weights=False`` serves a
    fully dense-dispatch baseline (no stack streaming, dense head).
    ``temperature`` > 0 samples every request at that temperature
    (top-``top_k`` truncated) with per-request seeds; default greedy.
    ``paged`` pages the KV cache into ``page_len``-token pages
    (``page_pool_tokens`` bounds each pool; out-of-pages admissions
    queue) — tokens are identical to the contiguous cache.
    ``prefill_chunk`` > 0 ingests prompts through batched
    ``prefill_chunk``-token prefill calls instead of teacher-forcing
    them one token per decode step (0 = the legacy walk); tokens are
    identical either way.
    ``prefix_reuse`` (with ``paged``) maps requests' matching prompt
    prefixes copy-on-write onto already-resident KV pages and skips
    their prefill; ``preempt`` commits live pages only and reclaims by
    preempting + recomputing the youngest slot when the pool runs dry.
    Tokens are identical with both on or off.
    ``deadline_ms`` expires requests that miss their latency budget;
    ``max_queue`` / ``ttft_budget_ms`` shed arrivals under overload
    (``ServeOverloaded`` — counted, not fatal); ``audit`` runs the
    step-level invariant auditor + packed-tensor integrity scan;
    ``faults`` injects a seeded ``repro.serve.FaultPlan`` (chaos
    testing — see DESIGN_SERVING.md §Failure semantics).
    ``trace_out`` / ``events_out`` / ``metrics_out`` write a profiler
    trace directory (the engine's ``serve.*`` step and phase spans beside
    the device's operations; Perfetto-viewable), the structured JSONL
    event log, and the metrics snapshot (`.prom` for Prometheus text,
    else JSON) — see DESIGN_SERVING.md
    §Observability.  All three default off; off is bit-identical.
    """
    eng = ServeEngine.from_arch(arch, smoke=smoke, num_slots=slots,
                                max_len=max_len, sparsity=sparsity,
                                head_sparsity=head_sparsity,
                                seed=seed, model_parallel=model_parallel,
                                devices=devices,
                                stream_weights=stream_weights,
                                bitmap_head=stream_weights, top_k=top_k,
                                paged=paged, page_len=page_len,
                                page_pool_tokens=page_pool_tokens,
                                prefill_chunk=prefill_chunk,
                                prefix_reuse=prefix_reuse,
                                preempt=preempt,
                                deadline_ms=deadline_ms,
                                max_queue=max_queue,
                                ttft_budget_ms=ttft_budget_ms,
                                max_preempts=max_preempts,
                                audit=audit, faults=faults,
                                trace_out=trace_out,
                                events_out=events_out,
                                metrics_out=metrics_out)
    trace = make_trace(eng, requests, rate, seed, max_new=max_new)
    rep, shed_at_submit = run_trace(eng, trace, temperature)
    for path in eng.close():
        if verbose:
            print(f"telemetry written: {path}")
    if verbose:
        ws = rep["weight_stream"]
        print(f"weight stream: {ws['packed_tensors']} tensors packed, "
              f"{ws['fallback_tensors']} dense fallbacks | modeled "
              f"per-step weight HBM {ws['sparse_bytes_per_step']/1e6:.2f}MB"
              f" vs dense {ws['dense_bytes_per_step']/1e6:.2f}MB "
              f"({ws['reduction']:.2f}x)")
        if rep["head_fallback"]:
            print(f"  head fallback: {rep['head_fallback']}")
        if sparsity > 0:
            print(f"serving at {eng.weight_sparsity:.2%} weight sparsity "
                  f"(head compression {eng.head_compression:.2f}x)")
        pf = rep["prefill"]
        if pf["enabled"]:
            tt = rep["ttft"]
            print(f"prefill: {pf['calls']} chunk calls ({pf['chunk']} "
                  f"tokens) over {pf['prefill_steps']} prefill + "
                  f"{pf['decode_steps']} decode steps | TTFT split p50 "
                  f"queue {tt['queue_s']['p50'] * 1e3:.1f}ms / prefill "
                  f"{tt['prefill_s']['p50'] * 1e3:.1f}ms / first decode "
                  f"{tt['first_decode_s']['p50'] * 1e3:.1f}ms")
        elif pf["fallback"]:
            print(f"  prefill fallback: {pf['fallback']}")
        lat, ftl = rep["latency_s"], rep["first_token_s"]
        pg = rep["paging"]
        if pg["paged"]:
            print(f"paged KV: {pg['pages_peak']} peak / "
                  f"{pg['pages_total']} pool pages ({pg['page_len']} "
                  f"tokens each) | reserved KV "
                  f"{pg['reserved_kv_bytes']/1e3:.1f}kB vs contiguous "
                  f"{pg['contiguous_kv_bytes']/1e3:.1f}kB "
                  f"({pg['reserved_reduction']:.2f}x)")
        elif pg["fallback"]:
            print(f"  paging fallback: {pg['fallback']}")
        pr = rep["prefix_reuse"]
        if pr["enabled"]:
            split = ""
            if pr["hit_requests"] and pr["miss_requests"]:
                split = (f" | TTFT p50 hit "
                         f"{pr['ttft_hit_s']['p50'] * 1e3:.1f}ms vs miss "
                         f"{pr['ttft_miss_s']['p50'] * 1e3:.1f}ms")
            print(f"prefix reuse: {pr['hits']} hits / {pr['misses']} "
                  f"misses ({pr['hit_tokens']} tokens adopted, "
                  f"{pr['forks']} COW forks, {pr['evictions']} "
                  f"evictions){split}")
        elif pr["fallback"]:
            print(f"  prefix-reuse fallback: {pr['fallback']}")
        pe = pr["preempt"]
        if pe["enabled"]:
            print(f"preemption: {pe['count']} preempts, "
                  f"{pe['recomputed_tokens']} tokens recomputed")
        elif pe["fallback"]:
            print(f"  preempt fallback: {pe['fallback']}")
        lc = rep["lifecycle"]
        shed = lc["shed"] + shed_at_submit
        if lc["cancelled"] or lc["expired"] or shed:
            print(f"lifecycle: {lc['cancelled']} cancelled / "
                  f"{lc['expired']} expired / {shed} shed "
                  f"({lc['wasted_tokens']} tokens wasted)")
        if lc["quarantined"]:
            print(f"  quarantined tensors: "
                  f"{', '.join(sorted(lc['quarantined']))}")
        if "faults" in lc:
            fs = lc["faults"]
            print(f"fault injection: {fs['fired']}/{fs['planned']} "
                  f"faults fired (seed {fs['seed']})")
        if "audit" in lc:
            au = lc["audit"]
            print(f"audit: {au['steps_checked']} steps checked, "
                  f"{au['integrity_scans']} integrity scans over "
                  f"{au['checksummed_tensors']} tensors, 0 violations")
        print(f"{rep['requests']} requests / {rep['generated_tokens']} "
              f"tokens in {rep['wall_s']:.2f}s over {slots} slots "
              f"(occupancy {rep['slot_occupancy']:.0%})")
        print(f"  throughput {rep['tok_per_s']:.1f} tok/s | latency "
              f"p50 {lat['p50'] * 1e3:.1f}ms p99 {lat['p99'] * 1e3:.1f}ms "
              f"| first-token p50 {ftl['p50'] * 1e3:.1f}ms "
              f"p99 {ftl['p99'] * 1e3:.1f}ms")
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step (Poisson)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--head-sparsity", type=float, default=None,
                    help="LM-head prune level before bitmap packing "
                         "(default: --sparsity; 0 = exact dense head)")
    ap.add_argument("--dense-stack", action="store_true",
                    help="disable all bitmap weight streaming (stack and "
                         "head): a fully dense-dispatch baseline")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="default top-k truncation for sampled requests "
                         "(0 = off; requests may override per-submit)")
    ap.add_argument("--paged", action="store_true",
                    help="page the KV cache (fixed-size pages + per-slot "
                         "page tables; reserved bytes scale with live "
                         "tokens)")
    ap.add_argument("--page-len", type=int, default=16,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--page-pool-tokens", type=int, default=None,
                    help="bound each page pool to this many tokens "
                         "(default: worst case; smaller pools queue "
                         "admissions when pages run out)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="ingest prompts in batched chunks of this many "
                         "tokens per prefill call (0 = legacy teacher-"
                         "forcing through decode steps)")
    ap.add_argument("--prefix-reuse", action="store_true",
                    help="share matching prompt prefixes copy-on-write "
                         "across requests (with --paged): cache hits "
                         "skip prefill entirely")
    ap.add_argument("--preempt", action="store_true",
                    help="commit live pages only and reclaim by "
                         "preempting + recomputing the youngest slot "
                         "when the pool runs dry (with --paged)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget from arrival-due to "
                         "completion; misses end EXPIRED (typed "
                         "DeadlineExceeded in request.result())")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="shed arrivals once this many requests are "
                         "queued (typed ServeOverloaded; counted in "
                         "report()['lifecycle'])")
    ap.add_argument("--ttft-budget-ms", type=float, default=None,
                    help="shed arrivals when estimated TTFT exceeds this "
                         "budget (queue work / measured step rate)")
    ap.add_argument("--max-preempts", type=int, default=8,
                    help="preemption bound: a request preempted this many "
                         "times re-admits pinned (worst-case page "
                         "commitment, never victimized again)")
    ap.add_argument("--audit", action="store_true",
                    help="run the step-level invariant auditor + packed-"
                         "tensor integrity scan every step (corruption "
                         "quarantines to dense + deterministic replay)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded FaultPlan.chaos() fault schedule "
                         "(page squeezes, forced preempts, eviction "
                         "storms, NaN logits, bitflips); implies --audit")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="run a profiler session over the serving loop "
                         "and write it under DIR (serve.* step and phase "
                         "spans beside the device's operations; open "
                         "plugins/profile/*/perfetto_trace.json.gz in "
                         "ui.perfetto.dev)")
    ap.add_argument("--events-out", default=None,
                    help="write the structured JSONL event log "
                         "(lifecycle transitions, fallbacks, faults, "
                         "audit violations)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a metrics snapshot at exit: Prometheus "
                         "text if the path ends in .prom, else JSON")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()
    faults = (FaultPlan.chaos(seed=args.chaos_seed)
              if args.chaos_seed is not None else None)
    serve_trace(args.arch, smoke=args.smoke, slots=args.slots,
                requests=args.requests, rate=args.rate,
                max_len=args.max_len, sparsity=args.sparsity,
                head_sparsity=args.head_sparsity,
                stream_weights=not args.dense_stack,
                temperature=args.temperature, top_k=args.top_k,
                paged=args.paged, page_len=args.page_len,
                page_pool_tokens=args.page_pool_tokens,
                prefill_chunk=args.prefill_chunk,
                prefix_reuse=args.prefix_reuse, preempt=args.preempt,
                deadline_ms=args.deadline_ms, max_queue=args.max_queue,
                ttft_budget_ms=args.ttft_budget_ms,
                max_preempts=args.max_preempts,
                audit=args.audit or faults is not None, faults=faults,
                trace_out=args.trace_out, events_out=args.events_out,
                metrics_out=args.metrics_out,
                seed=args.seed, model_parallel=args.model_parallel)


if __name__ == "__main__":
    main()
