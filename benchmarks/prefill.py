"""Chunked batched prefill: the TTFT proof sweep.

Drives the continuous-batching engine over a ``prefill_chunk`` sweep on
a *long-prompt* Poisson trace — the regime the teacher-forcing admission
path is worst at, since every prompt token used to cost one full-batch
decode step of latency before the first generated token.  Each cell
reports, against the ``prefill_chunk=0`` teacher-forcing baseline on the
identical trace:

* TTFT p50/p99 (arrival -> first generated token) plus the engine's
  queue / prefill / first-decode decomposition — chunked prefill is
  token-lossless, so any TTFT delta is pure admission mechanics;
* measured tok/s (generated tokens over the whole run) — the chunk
  calls replace prompt-walk decode steps, so throughput should not
  regress while TTFT drops;
* chunk-call accounting: calls, engine steps prefill vs decode, lane
  utilization of the padded (slots × chunk) call batch.

``--out BENCH_serve.json`` merges a ``prefill`` section into the
existing bench file (scripts/ci.sh runs a smoke cell every CI pass).
"""
from __future__ import annotations

import argparse

try:                                    # script: benchmarks/ on sys.path
    from _bench_io import bench_timer, merge_section
except ImportError:                     # package: imported from repo root
    from benchmarks._bench_io import bench_timer, merge_section
import jax

from repro.configs import get_config, get_smoke_config
from repro.serve import ServeEngine, poisson_trace


def _trace(cfg, requests, rate, max_len, seed):
    """Long prompts, short generations: prompts 3/4 of max_len, budgets
    a handful of tokens — TTFT dominated by prompt ingestion."""
    plo = max(2, max_len // 2)
    phi = max(plo, 3 * max_len // 4)
    hi = max(2, min(8, max_len - phi))
    return poisson_trace(requests, rate=rate, seed=seed,
                         vocab_size=cfg.vocab_size, prompt_len=(plo, phi),
                         max_new=(1, hi))


def _run(cfg, trace, *, slots, max_len, sparsity, seed, prefill_chunk,
         paged=False, page_len=16):
    eng = ServeEngine(cfg, num_slots=slots, max_len=max_len,
                      sparsity=sparsity, seed=seed, head_sparsity=0.0,
                      prefill_chunk=prefill_chunk, paged=paged,
                      page_len=page_len)
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**spec)
        return eng.run()


def sweep(arch: str = "olmo-1b", smoke: bool = True,
          chunks=(8, 16), slots: int = 4, requests: int = 8,
          rate: float = 0.3, max_len: int = 96, sparsity: float = 0.5,
          paged: bool = False, seed: int = 0, repeats: int = 3,
          verbose: bool = True) -> dict:
    """``prefill_chunk`` sweep vs the teacher-forcing baseline on one
    identical long-prompt trace (tokens are bit-identical across the
    whole row — the sweep measures admission latency, nothing else).

    Each cell keeps the best-TTFT run of ``repeats`` (smoke cells finish
    in well under a second, so single runs are scheduler-noise-bound).
    """
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    trace = _trace(cfg, requests, rate, max_len, seed)
    mean_prompt = sum(len(t["prompt"]) for t in trace) / len(trace)

    def best(chunk):
        return min((_run(cfg, trace, slots=slots, max_len=max_len,
                         sparsity=sparsity, seed=seed, prefill_chunk=chunk,
                         paged=paged)
                    for _ in range(repeats)),
                   key=lambda r: r["first_token_s"]["p50"])

    base = best(0)
    rows = []
    for chunk in chunks:
        rep = best(chunk)
        pf, tt = rep["prefill"], rep["ttft"]
        row = {
            "arch": arch, "slots": slots, "prefill_chunk": chunk,
            "mean_prompt_len": mean_prompt, "paged": paged,
            "ttft_p50_s": rep["first_token_s"]["p50"],
            "ttft_p99_s": rep["first_token_s"]["p99"],
            "ttft_p50_baseline_s": base["first_token_s"]["p50"],
            "ttft_p50_speedup": (base["first_token_s"]["p50"]
                                 / rep["first_token_s"]["p50"]),
            "ttft_split_p50_s": {k: tt[k]["p50"] for k in tt},
            "tok_per_s": rep["tok_per_s"],
            "tok_per_s_baseline": base["tok_per_s"],
            "tok_per_s_ratio": rep["tok_per_s"] / base["tok_per_s"],
            "chunk_calls": pf["calls"],
            "prefill_steps": pf["prefill_steps"],
            "decode_steps": pf["decode_steps"],
            "baseline_steps": base["steps"],
            "lane_utilization": pf["lane_utilization"],
        }
        rows.append(row)
        if verbose:
            print(f"  {arch:10s} slots={slots} chunk={chunk:3d} | TTFT "
                  f"p50 {row['ttft_p50_s'] * 1e3:7.1f}ms vs baseline "
                  f"{row['ttft_p50_baseline_s'] * 1e3:7.1f}ms "
                  f"({row['ttft_p50_speedup']:.2f}x) | "
                  f"{row['tok_per_s']:7.1f} tok/s "
                  f"({row['tok_per_s_ratio']:.2f}x) | "
                  f"{row['chunk_calls']} calls, lanes "
                  f"{row['lane_utilization']:.0%}")
    headline = {
        "arch": arch,
        "mean_prompt_len": mean_prompt,
        "ttft_p50_speedup_best": max(r["ttft_p50_speedup"] for r in rows),
        "tok_per_s_ratio_worst": min(r["tok_per_s_ratio"] for r in rows),
    }
    if verbose:
        print(f"  headline: TTFT p50 {headline['ttft_p50_speedup_best']:.2f}x"
              f" faster than teacher-forcing on ~{mean_prompt:.0f}-token "
              f"prompts; tok/s worst ratio "
              f"{headline['tok_per_s_ratio_worst']:.2f}")
    return {"rows": rows, "headline": headline}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--chunks", type=int, nargs="+", default=[8, 16])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.3)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--paged", action="store_true",
                    help="run the sweep on the paged KV cache (prefill "
                         "bulk-maps each chunk's pages)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="merge a 'prefill' section into this JSON file "
                         "(e.g. BENCH_serve.json)")
    args = ap.parse_args()
    with bench_timer("prefill") as timing:
        result = sweep(args.arch, smoke=args.smoke,
                       chunks=tuple(args.chunks), slots=args.slots,
                       requests=args.requests, rate=args.rate,
                       max_len=args.max_len, sparsity=args.sparsity,
                       paged=args.paged, seed=args.seed,
                       repeats=args.repeats)
    if args.out:
        merge_section(args.out, "prefill", result, wall_s=timing.wall_s)


if __name__ == "__main__":
    main()
