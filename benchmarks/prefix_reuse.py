"""Shared-prefix COW page reuse + recompute-on-preempt: the data-reuse
proof sweep.

Drives the paged continuous-batching engine over a *shared-prefix*
Poisson trace — a handful of "system prompts" each reused by many
requests with unique tails, the serving analogue of the paper's SIDR
coordination (data fetched once, reused by everyone).  Four cells on
the identical trace, in two pool regimes:

* **roomy pool** (strict worst case, no admission queueing): reuse off
  vs on — repeated prefixes are adopted copy-on-write from resident
  pages and skip prefill, so TTFT p50 on a cache *hit* falls below the
  cold-*miss* p50 (the engine's hit/miss TTFT split proves it);
* **tight pool** (half the worst case): preempt+reuse off vs on at the
  *same* pool size — relaxed live-page commitment with recompute-on-
  preempt reclamation raises slot occupancy, and tokens stay identical
  to the baseline in every cell (the acceptance matrix).

``--out BENCH_serve.json`` merges a ``prefix_reuse`` section into the
existing bench file without clobbering the paging/prefill/arch sections
(scripts/ci.sh runs a smoke cell every CI pass).
"""
from __future__ import annotations

import argparse

try:                                    # script: benchmarks/ on sys.path
    from _bench_io import bench_timer, merge_section
except ImportError:                     # package: imported from repo root
    from benchmarks._bench_io import bench_timer, merge_section

import numpy as np
import jax

from repro.configs import get_config, get_smoke_config
from repro.serve import ServeEngine


def shared_prefix_trace(n_requests: int, rate: float, seed: int,
                        vocab_size: int, n_prefixes: int = 2,
                        prefix_len: int = 16, tail_len: int = 2,
                        max_new=(3, 8)):
    """Poisson arrivals where each request picks one of ``n_prefixes``
    shared system prompts and appends a unique tail — after each
    prefix's first (cold) request, every later one can hit the cache."""
    assert rate > 0
    r = np.random.default_rng(seed)
    prefixes = [[int(x) for x in r.integers(0, vocab_size, prefix_len)]
                for _ in range(n_prefixes)]
    t, out = 0.0, []
    for i in range(n_requests):
        t += float(r.exponential(1.0 / rate))
        pre = prefixes[i % n_prefixes]
        tail = [int(x) for x in r.integers(0, vocab_size, tail_len)]
        out.append({"prompt": pre + tail,
                    "max_new_tokens": int(r.integers(max_new[0],
                                                     max_new[1],
                                                     endpoint=True)),
                    "arrival": t})
    return out


def _run(cfg, trace, *, slots, max_len, sparsity, seed, page_len,
         pool_tokens, prefill_chunk, prefix_reuse, preempt):
    eng = ServeEngine(cfg, num_slots=slots, max_len=max_len,
                      sparsity=sparsity, seed=seed, head_sparsity=0.0,
                      paged=True, page_len=page_len,
                      page_pool_tokens=pool_tokens,
                      prefill_chunk=prefill_chunk,
                      prefix_reuse=prefix_reuse, preempt=preempt)
    reqs = []
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            reqs.append(eng.submit(**spec))
        rep = eng.run()
    return rep, [r.tokens for r in reqs]


def sweep(arch: str = "olmo-1b", smoke: bool = True, slots: int = 4,
          requests: int = 10, rate: float = 0.4, max_len: int = 64,
          sparsity: float = 0.5, page_len: int = 8,
          pool_tokens: int | None = None, prefill_chunk: int = 8,
          prefix_len: int = 16, seed: int = 0, repeats: int = 3,
          verbose: bool = True) -> dict:
    """Two paired comparisons on one identical shared-prefix trace,
    tokens identical across every cell (reuse and recompute are exact):

    * **roomy pool** (worst case, no queueing confound): reuse on vs
      off — the hit-vs-miss TTFT split isolates the skipped prefill;
    * **tight pool** (``pool_tokens``, default half the strict worst
      case): preempt+reuse on vs off — equal pool size, so the
      occupancy delta isolates relaxed live-page commitment.

    Each cell keeps the best-TTFT run of ``repeats``."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    trace = shared_prefix_trace(requests, rate, seed, cfg.vocab_size,
                                prefix_len=prefix_len,
                                max_new=(max(3, max_len // 8),
                                         max(3, max_len // 4)))
    worst = max(len(t["prompt"]) + t["max_new_tokens"] - 1
                for t in trace)
    if pool_tokens is None:
        pool_tokens = slots * worst // 2

    def best(pool, prefix_reuse, preempt):
        runs = [_run(cfg, trace, slots=slots, max_len=max_len,
                     sparsity=sparsity, seed=seed, page_len=page_len,
                     pool_tokens=pool, prefill_chunk=prefill_chunk,
                     prefix_reuse=prefix_reuse, preempt=preempt)
                for _ in range(repeats)]
        toks = runs[0][1]
        assert all(t == toks for _, t in runs), "nondeterministic run"
        return min((r for r, _ in runs),
                   key=lambda r: r["first_token_s"]["p50"]), toks

    base, base_toks = best(None, False, False)
    reuse, reuse_toks = best(None, True, False)
    tight, tight_toks = best(pool_tokens, False, False)
    both, both_toks = best(pool_tokens, True, True)
    assert reuse_toks == base_toks, "prefix reuse changed tokens"
    assert tight_toks == base_toks, "tight pool changed tokens"
    assert both_toks == base_toks, "preemption changed tokens"

    pr, pb = reuse["prefix_reuse"], both["prefix_reuse"]
    result = {
        "arch": arch, "slots": slots, "requests": requests,
        "page_len": page_len, "pool_tokens": pool_tokens,
        "prefix_len": prefix_len, "prefill_chunk": prefill_chunk,
        "tokens_identical": True,
        "baseline": {
            "ttft_p50_s": base["first_token_s"]["p50"],
            "tok_per_s": base["tok_per_s"],
            "slot_occupancy": base["slot_occupancy"],
        },
        "reuse": {
            "ttft_hit_p50_s": pr["ttft_hit_s"]["p50"],
            "ttft_miss_p50_s": pr["ttft_miss_s"]["p50"],
            "ttft_hit_speedup": (pr["ttft_miss_s"]["p50"]
                                 / pr["ttft_hit_s"]["p50"]),
            "hits": pr["hits"], "misses": pr["misses"],
            "hit_tokens": pr["hit_tokens"], "forks": pr["forks"],
            "evictions": pr["evictions"],
            "tok_per_s": reuse["tok_per_s"],
        },
        "tight_baseline": {
            "slot_occupancy": tight["slot_occupancy"],
            "tok_per_s": tight["tok_per_s"],
        },
        "reuse_preempt": {
            "slot_occupancy": both["slot_occupancy"],
            "occupancy_gain": (both["slot_occupancy"]
                               / tight["slot_occupancy"]
                               if tight["slot_occupancy"] else 1.0),
            "preemptions": pb["preempt"]["count"],
            "recomputed_tokens": pb["preempt"]["recomputed_tokens"],
            "evictions": pb["evictions"],
            "tok_per_s": both["tok_per_s"],
        },
    }
    if verbose:
        r, p = result["reuse"], result["reuse_preempt"]
        print(f"  {arch:10s} slots={slots} | TTFT p50 hit "
              f"{r['ttft_hit_p50_s'] * 1e3:6.1f}ms vs miss "
              f"{r['ttft_miss_p50_s'] * 1e3:6.1f}ms "
              f"({r['ttft_hit_speedup']:.2f}x) | {r['hits']} hits / "
              f"{r['misses']} misses, {r['hit_tokens']} tokens adopted, "
              f"{r['forks']} forks, {r['evictions']} evictions")
        print(f"  tight pool {pool_tokens}tok: occupancy "
              f"{result['tight_baseline']['slot_occupancy']:.0%} -> "
              f"{p['slot_occupancy']:.0%} with reuse+preempt "
              f"({p['occupancy_gain']:.2f}x at equal pool), "
              f"{p['preemptions']} preempts / {p['recomputed_tokens']} "
              f"tokens recomputed | tokens identical across all cells")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--rate", type=float, default=0.4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--page-len", type=int, default=8)
    ap.add_argument("--pool-tokens", type=int, default=None,
                    help="per-pool page budget in tokens (default: half "
                         "the strict worst case, so preemption engages)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="shared system-prompt length in tokens")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="merge a 'prefix_reuse' section into this JSON "
                         "file (e.g. BENCH_serve.json)")
    args = ap.parse_args()
    with bench_timer("prefix_reuse") as timing:
        result = sweep(args.arch, smoke=args.smoke, slots=args.slots,
                       requests=args.requests, rate=args.rate,
                       max_len=args.max_len, sparsity=args.sparsity,
                       page_len=args.page_len,
                       pool_tokens=args.pool_tokens,
                       prefill_chunk=args.prefill_chunk,
                       prefix_len=args.prefix_len, seed=args.seed,
                       repeats=args.repeats)
    if args.out:
        merge_section(args.out, "prefix_reuse", result,
                      wall_s=timing.wall_s)


if __name__ == "__main__":
    main()
