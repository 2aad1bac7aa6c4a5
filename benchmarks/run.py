"""Benchmark entry: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus the per-benchmark
summaries.  ``python -m benchmarks.run [--fast]``.
"""
from __future__ import annotations

import argparse
import sys
import time


def _csv(name: str, us: float, derived: str):
    print(f"CSV,{name},{us:.1f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sampling for quick regression runs")
    args = ap.parse_args()

    sys.path.insert(0, "src")
    from benchmarks import (comparison_table, kernel_bench, mobilenet_pw,
                            roofline, sparse_serving, sparsity_sweep)

    print("== [1/6] MobileNetV2 PW layers (paper Fig. 6 / §III-A) ==")
    t0 = time.time()
    _, s1 = mobilenet_pw.run(max_row_tiles=2 if args.fast else 8,
                             verbose=not args.fast)
    _csv("mobilenet_pw", (time.time() - t0) * 1e6,
         f"mapm={s1['avg_mapm_byte_per_mac']:.3f};"
         f"util={s1['overall_utilization']:.3f};"
         f"speedup={s1['overall_speedup']:.2f};"
         f"sram_cut={s1['sram_reduction_vs_sparten']:.3f}")
    for k, v in s1.items():
        print(f"  {k:30s} {v:.4f}")

    print("\n== [2/6] Random-matrix sparsity sweep (paper Fig. 7) ==")
    t0 = time.time()
    _, s2 = sparsity_sweep.run(size=256 if args.fast else 1024,
                               max_row_tiles=2 if args.fast else 4,
                               verbose=not args.fast)
    _csv("sparsity_sweep", (time.time() - t0) * 1e6,
         f"min_util_mid={s2['mid_range_min_utilization']:.3f}")
    for k, v in s2.items():
        print(f"  {k:30s} {v:.4f}")

    print("\n== [3/6] Comparison table + breakdowns (Table I, Fig. 8/9) ==")
    t0 = time.time()
    _, s3 = comparison_table.run()
    _csv("comparison_table", (time.time() - t0) * 1e6,
         f"tops_w={s3['ours_tops_per_watt']:.3f};"
         f"vs_sparten={s3['vs_sparten_style_energy_ratio']:.2f}x")

    print("\n== [4/6] Kernel HBM-traffic microbench (TPU adaptation) ==")
    t0 = time.time()
    kernel_bench.run()
    _csv("kernel_bench", (time.time() - t0) * 1e6, "see rows above")

    print("\n== [5/6] Roofline from dry-run artifacts ==")
    t0 = time.time()
    roofline.main()
    _csv("roofline", (time.time() - t0) * 1e6, "see table above")

    print("\n== [6/6] Sparse serving (paper technique on decode) ==")
    t0 = time.time()
    sparse_serving.main()
    _csv("sparse_serving", (time.time() - t0) * 1e6, "see rows above")


if __name__ == "__main__":
    main()
