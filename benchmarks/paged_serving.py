"""Paged KV cache: the reserved-bytes proof sweep.

Drives the continuous-batching engine over a (page_len × slots) grid on
a mixed-length Poisson trace — short and long requests interleaved, the
regime where the contiguous cache's worst-case ``num_slots × max_len``
reservation hurts most — once paged and once contiguous.  Each cell
reports:

* reserved KV-cache bytes, paged pool vs contiguous worst case (the
  paged pool is sized to the *trace's* worst per-request need, so the
  reduction is what right-sizing actually buys, with out-of-pages
  admissions queueing rather than crashing);
* measured tok/s for both engines on the identical trace (paging is
  token-lossless, so any delta is pure gather/scatter dispatch);
* allocator stats: peak pages, fragmentation of in-use pages.

``--out BENCH_serve.json`` merges a ``paging`` section into the existing
bench file (scripts/ci.sh runs a smoke cell every CI pass, after the
bitmap-streaming sweep writes the base file).
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
    """Mixed-length trace: prompts 1..4, budgets 1..24 tokens — the
    serving regime where a long-capacity engine (``max_len`` is the
    *ceiling*, not the typical request) pays worst-case contiguous
    reservation for mostly-short traffic."""
    hi = max(2, min(24, max_len - 4))
    return poisson_trace(requests, rate=rate, seed=seed,
                         vocab_size=cfg.vocab_size, prompt_len=(1, 4),
                         max_new=(1, hi))


def _run(cfg, trace, *, slots, max_len, sparsity, seed, paged,
         page_len=0, pool_tokens=None):
    eng = ServeEngine(cfg, num_slots=slots, max_len=max_len,
                      sparsity=sparsity, seed=seed, paged=paged,
                      page_len=page_len, page_pool_tokens=pool_tokens,
                      head_sparsity=0.0)
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**spec)
        return eng.run()


def sweep(arch: str = "olmo-1b", smoke: bool = True,
          page_lens=(8, 16), slots_list=(2, 4), requests: int = 12,
          rate: float = 0.7, max_len: int = 256, sparsity: float = 0.5,
          seed: int = 0, repeats: int = 3, verbose: bool = True) -> dict:
    """(page_len × slots) grid, paged vs contiguous on identical traces.

    The paged pool is budgeted to ``slots ×`` the trace's worst single
    request (rounded up to pages) — enough that admission never queues
    on slot-count alone, small enough that reserved bytes track live
    tokens instead of ``slots × max_len``."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    rows = []
    for slots in slots_list:
        trace = _trace(cfg, requests, rate, max_len, seed)
        worst = max(len(t["prompt"]) + t["max_new_tokens"] - 1
                    for t in trace)
        cont = max(
            (_run(cfg, trace, slots=slots, max_len=max_len,
                  sparsity=sparsity, seed=seed, paged=False)
             for _ in range(repeats)), key=lambda r: r["tok_per_s"])
        for page_len in page_lens:
            pool_tokens = slots * (-(-worst // page_len)) * page_len
            paged = max(
                (_run(cfg, trace, slots=slots, max_len=max_len,
                      sparsity=sparsity, seed=seed, paged=True,
                      page_len=page_len, pool_tokens=pool_tokens)
                 for _ in range(repeats)), key=lambda r: r["tok_per_s"])
            pg = paged["paging"]
            row = {
                "arch": arch, "slots": slots, "page_len": page_len,
                "max_len": max_len, "trace_worst_need": worst,
                "pool_tokens": pool_tokens,
                "tok_per_s": paged["tok_per_s"],
                "tok_per_s_contiguous": cont["tok_per_s"],
                "tok_per_s_ratio": paged["tok_per_s"] / cont["tok_per_s"],
                "reserved_kv_bytes": pg["reserved_kv_bytes"],
                "contiguous_kv_bytes": cont["paging"]["reserved_kv_bytes"],
                "reserved_reduction": (
                    cont["paging"]["reserved_kv_bytes"]
                    / pg["reserved_kv_bytes"]),
                "pages_peak": pg["pages_peak"],
                "pages_total": pg["pages_total"],
            }
            rows.append(row)
            if verbose:
                print(f"  {arch:10s} slots={slots} page_len={page_len:3d}"
                      f" | {row['tok_per_s']:8.1f} tok/s (contiguous "
                      f"{row['tok_per_s_contiguous']:8.1f}, "
                      f"{row['tok_per_s_ratio']:.2f}x) | reserved KV "
                      f"{row['reserved_kv_bytes']/1e3:7.1f}kB vs "
                      f"{row['contiguous_kv_bytes']/1e3:7.1f}kB "
                      f"({row['reserved_reduction']:.2f}x) | pages "
                      f"{row['pages_peak']}/{row['pages_total']}")
    headline = {
        "arch": arch,
        "reserved_reduction_min": min(r["reserved_reduction"]
                                      for r in rows),
        "tok_per_s_ratio_worst": min(r["tok_per_s_ratio"] for r in rows),
    }
    if verbose:
        print(f"  headline: >= {headline['reserved_reduction_min']:.2f}x "
              f"less KV reserved than slots x max_len; paged/contiguous "
              f"tok/s worst {headline['tok_per_s_ratio_worst']:.2f}")
    return {"rows": rows, "headline": headline}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--page-lens", type=int, nargs="+", default=[8, 16])
    ap.add_argument("--slots", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=0.7)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="merge a 'paging' section into this JSON file "
                         "(e.g. BENCH_serve.json)")
    args = ap.parse_args()
    with bench_timer("paging") as timing:
        result = sweep(args.arch, smoke=args.smoke,
                       page_lens=tuple(args.page_lens),
                       slots_list=tuple(args.slots),
                       requests=args.requests, rate=args.rate,
                       max_len=args.max_len, sparsity=args.sparsity,
                       seed=args.seed, repeats=args.repeats)
    if args.out:
        merge_section(args.out, "paging", result, wall_s=timing.wall_s)


if __name__ == "__main__":
    main()
