"""Roofline report (deliverable g): three terms per (arch × shape × mesh).

Reads the dry-run JSONs and derives, per device:
    compute    = HLO_FLOPs / 197 TFLOP/s
    memory     = HLO_bytes / 819 GB/s
    collective = wire_bytes / (4 × 50 GB/s ICI links)
plus MODEL_FLOPS (6·N·D train / 2·N·D inference, N_active for MoE), the
useful-compute ratio, the dominant term, and a one-line "what would move
it".  Emits the markdown table EXPERIMENTS.md §Roofline embeds.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.configs import SHAPES
from repro.launch.hlo_analysis import V5E, peaks, roofline

def model_flops_per_device(rec: Dict) -> float:
    shape = SHAPES[rec["shape"]]
    n_active = rec["active_param_count"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / rec["num_devices"]


def advice(rec: Dict, terms: Dict) -> str:
    b = terms["bottleneck"]
    kind = SHAPES[rec["shape"]].kind
    if b == "compute":
        ratio = model_flops_per_device(rec) / max(rec["flops_per_device"], 1)
        if ratio < 0.5:
            return ("compute-bound with low useful ratio: cut remat "
                    "recompute / attention waste")
        return "compute-bound near useful-FLOP limit: healthy"
    if b == "memory":
        if kind == "decode":
            return ("decode weight streaming: compress weights (bitmap "
                    "kernel) or raise batch to amortise")
        return "reduce activation traffic: fuse, recompute less, bf16 stats"
    return "collective-bound: reshard to cut all-reduce volume / overlap"


def load_records(dryrun_dir: str = "results/dryrun") -> List[Dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def build_rows(recs: List[Dict]) -> List[Dict]:
    rows = []
    for rec in recs:
        # dry-run records are compiled for the 16x16 v5e production mesh
        terms = roofline(rec["flops_per_device"],
                         rec["hbm_bytes_per_device"],
                         rec["collectives"].get("wire_bytes", 0.0), V5E)
        mf = model_flops_per_device(rec)
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "compute_s": terms["compute_s"],
            "memory_s": terms["memory_s"],
            "collective_s": terms["collective_s"],
            "bottleneck": terms["bottleneck"],
            "model_flops_per_dev": mf,
            "useful_ratio": mf / max(rec["flops_per_device"], 1.0),
            "step_s": terms["step_time_overlapped_s"],
            # usable fraction of peak compute in the overlapped-ideal step
            "mfu_bound": (mf / peaks(V5E)["flops"]) / max(
                terms["step_time_overlapped_s"], 1e-30),
            "advice": advice(rec, terms),
        })
    return rows


def markdown_table(rows: List[Dict], mesh_filter: str = "16x16") -> str:
    out = ["| arch | shape | compute s | memory s | coll s | bound | "
           "useful | MFU-bound | what moves it |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["mesh"] != mesh_filter:
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r['collective_s']:.2e} | "
            f"{r['bottleneck']} | {r['useful_ratio']:.2f} | "
            f"{r['mfu_bound']:.2f} | {r['advice']} |")
    return "\n".join(out)


def main():
    recs = load_records()
    if not recs:
        print("no dry-run records found — run repro.launch.dryrun first")
        return
    rows = build_rows(recs)
    print(markdown_table(rows))
    print()
    # summary of bottleneck distribution
    from collections import Counter
    c = Counter(r["bottleneck"] for r in rows if r["mesh"] == "16x16")
    print("bottleneck distribution (single pod):", dict(c))
    worst = sorted((r for r in rows if r["mesh"] == "16x16"),
                   key=lambda r: r["mfu_bound"])[:3]
    print("worst MFU-bound cells:",
          [(r["arch"], r["shape"], round(r["mfu_bound"], 3))
           for r in worst])


if __name__ == "__main__":
    main()
