"""Overload behaviour: goodput under an over-subscribed Poisson trace.

Drives the engine with an arrival rate well past what its slots can
drain, twice on the same seeded trace:

* **baseline** — no admission control: every request queues, TTFT and
  latency grow without bound as the backlog builds, and every request
  eventually completes (late);
* **shedding** — ``max_queue`` bounds the due queue: excess arrivals
  end SHED with a typed ``ServeOverloaded`` (recorded, not fatal), and
  the requests that *are* admitted see bounded queues.

Each cell reports goodput (tokens delivered by requests that finished
within ``--slo-ms`` of coming due), the shed rate, latency / TTFT
percentiles, and wasted tokens.  The point of the comparison: under
overload, shedding trades completed-late tokens for within-SLO tokens —
goodput (not raw throughput) is the served-system metric.

``--out BENCH_serve.json`` merges an ``overload`` section into the
bench file, preserving the other tools' sections.
"""
from __future__ import annotations

import argparse

try:                                    # script: benchmarks/ on sys.path
    from _bench_io import bench_timer, merge_section
except ImportError:                     # package: imported from repo root
    from benchmarks._bench_io import bench_timer, merge_section
import jax

from repro.configs import get_config, get_smoke_config
from repro.serve import RequestState, ServeEngine, poisson_trace


def _cell(cfg, *, slots: int, requests: int, rate: float, max_len: int,
          sparsity: float, seed: int, slo_ms: float,
          max_queue: int | None) -> dict:
    eng = ServeEngine(cfg, num_slots=slots, max_len=max_len,
                      sparsity=sparsity, seed=seed,
                      max_queue=max_queue)
    hi = max(1, min(12, max_len - 4))
    trace = poisson_trace(requests, rate=rate, seed=seed,
                          vocab_size=cfg.vocab_size, prompt_len=(1, 4),
                          max_new=(max(1, hi // 2), hi))
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**spec)   # future arrivals: due-time shedding
        rep = eng.run()
    good = wasted = done = 0
    lat = []
    for r in eng.requests:
        if r.state is RequestState.DONE:
            done += 1
            lat.append(r.latency_s)
            if r.latency_s is not None and r.latency_s * 1e3 <= slo_ms:
                good += len(r.tokens)
            else:
                wasted += len(r.tokens)   # delivered, but past the SLO
    lc = rep["lifecycle"]
    dt = rep["wall_s"]
    return {
        "max_queue": max_queue,
        "requests": requests,
        "completed": done,
        "shed": lc["shed"],
        "shed_rate": lc["shed"] / requests,
        "generated_tokens": rep["generated_tokens"],
        "goodput_tok_per_s": good / dt if dt > 0 else None,
        "tok_per_s": rep["tok_per_s"],
        "within_slo_tokens": good,
        "late_tokens": wasted,
        "wasted_tokens": lc["wasted_tokens"],
        "latency_s": rep["latency_s"],
        "first_token_s": rep["first_token_s"],
    }


def sweep(arch: str = "olmo-1b", smoke: bool = True, slots: int = 2,
          requests: int = 24, rate: float = 4.0, max_len: int = 48,
          sparsity: float = 0.5, seed: int = 0, slo_ms: float = 200.0,
          max_queue: int = 4, verbose: bool = True) -> dict:
    """Baseline vs shedding on the same over-subscribed seeded trace."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    cells = {}
    for name, mq in (("baseline", None), ("shedding", max_queue)):
        cells[name] = _cell(cfg, slots=slots, requests=requests,
                            rate=rate, max_len=max_len, sparsity=sparsity,
                            seed=seed, slo_ms=slo_ms, max_queue=mq)
        if verbose:
            c = cells[name]
            gp = c["goodput_tok_per_s"]
            print(f"[{name:>8}] {c['completed']}/{requests} done, "
                  f"{c['shed']} shed ({c['shed_rate']:.0%}) | goodput "
                  f"{gp:.1f} tok/s (raw {c['tok_per_s']:.1f}) | "
                  f"p99 latency {c['latency_s']['p99'] * 1e3:.0f}ms"
                  if gp is not None else f"[{name:>8}] no cells")
    result = {"arch": arch, "slots": slots, "rate": rate,
              "slo_ms": slo_ms, "seed": seed, "cells": cells}
    if verbose:
        b, s = cells["baseline"], cells["shedding"]
        if b["goodput_tok_per_s"] and s["goodput_tok_per_s"]:
            print(f"goodput ratio shedding/baseline: "
                  f"{s['goodput_tok_per_s'] / b['goodput_tok_per_s']:.2f}x"
                  f" at {s['shed_rate']:.0%} shed")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrivals per decode step — deliberately "
                         "past what the slots can drain")
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--slo-ms", type=float, default=200.0,
                    help="end-to-end latency SLO defining goodput")
    ap.add_argument("--max-queue", type=int, default=4,
                    help="shedding cell's due-queue bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="merge an 'overload' section into this JSON "
                         "file (e.g. BENCH_serve.json)")
    args = ap.parse_args()
    with bench_timer("overload") as timing:
        result = sweep(args.arch, smoke=args.smoke, slots=args.slots,
                       requests=args.requests, rate=args.rate,
                       max_len=args.max_len, sparsity=args.sparsity,
                       slo_ms=args.slo_ms, max_queue=args.max_queue,
                       seed=args.seed)
    if args.out:
        merge_section(args.out, "overload", result, wall_s=timing.wall_s)


if __name__ == "__main__":
    main()
