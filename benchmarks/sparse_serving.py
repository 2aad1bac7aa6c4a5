"""Paper-technique serving analysis: bitmap-compressed weights on decode.

Decode is memory-bound (§Roofline): the step time is HBM traffic / BW.
This benchmark splits each decode cell's measured per-device traffic into
weight-streaming vs everything else (KV cache, activations) and applies
the *measured* bitmap-format compression (pack_bitmap at the paper's 75 %
global-L1 sparsity, including bitmap + row-offset overhead — the same
format the validated ``bitmap_spmm`` kernel consumes) to the weight term.

This is the TPU analogue of the paper's headline (86 % SRAM-access cut →
2.5× power efficiency): HBM-traffic cut → decode-step speed-up, largest
where weight streaming dominates (small batch / long context).
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

from repro.configs import SHAPES, get_config
from repro.launch.hlo_analysis import V5E, peaks
from repro.sparse.format import pack_bitmap
from repro.sparse.pruning import per_tensor_prune


def measured_compression(sparsity: float = 0.75, seed: int = 0) -> float:
    """Bitmap-format compression at the paper's sparsity, with overheads."""
    import jax.numpy as jnp
    r = np.random.default_rng(seed)
    w = jnp.asarray(r.standard_normal((1024, 1024)), jnp.bfloat16)
    wp = per_tensor_prune(w, sparsity)
    return pack_bitmap(np.asarray(wp, np.float32).astype(np.float16),
                       block=(128, 128)).compression


def matmul_weight_bytes_per_device(arch: str, n_model_shards: int = 16,
                                   itemsize: int = 2) -> float:
    """Streamed matmul weights per decode step per device (embeddings are
    gathered, not streamed; tied LM head is streamed)."""
    cfg = get_config(arch)
    from repro.models.model import param_shapes
    total = 0
    for path, shape in _walk(param_shapes(cfg)):
        if "embed" in path and cfg.tie_embeddings:
            total += math.prod(shape)      # tied head is streamed
        elif "embed" in path:
            continue
        elif len(shape) >= 2:
            total += math.prod(shape)
    return total * itemsize / n_model_shards


def _walk(d, prefix=""):
    for k, v in d.items():
        p = f"{prefix}/{k}"
        if isinstance(v, dict):
            yield from _walk(v, p)
        else:
            yield p, v


def run(cells=(("gemma3-12b", "decode_2k_b8"),
              ("internvl2-76b", "decode_2k_b8"),
              ("gemma3-12b", "decode_32k"), ("gemma3-12b", "long_500k"),
              ("internvl2-76b", "decode_32k"), ("rwkv6-3b", "long_500k")),
        dryrun_dir: str = "results/dryrun", sparsity: float = 0.75,
        verbose: bool = True):
    comp = measured_compression(sparsity)
    rows = []
    for arch, shape in cells:
        path = os.path.join(dryrun_dir, f"{arch}__{shape}__16x16.json")
        if not os.path.exists(path):
            continue
        rec = json.load(open(path))
        total = rec["hbm_bytes_per_device"]
        wbytes = matmul_weight_bytes_per_device(arch)
        hbm_bw = peaks(V5E)["hbm_bw"]     # the dry-run's 16x16 v5e mesh
        dense_t = total / hbm_bw
        sparse_total = total - wbytes + wbytes / comp
        sparse_t = sparse_total / hbm_bw
        rows.append({
            "arch": arch, "shape": shape,
            "total_bytes": total, "weight_bytes": wbytes,
            "weight_share": wbytes / total,
            "compression": comp,
            "step_dense_s": dense_t, "step_sparse_s": sparse_t,
            "speedup": dense_t / sparse_t,
        })
        if verbose:
            r = rows[-1]
            print(f"  {arch:16s} {shape:11s} weights {r['weight_share']:.0%}"
                  f" of {total/1e9:.1f}GB -> step {dense_t*1e3:.2f}ms"
                  f" => {sparse_t*1e3:.2f}ms ({r['speedup']:.2f}x)")
    return rows, {"bitmap_compression": comp}


def serve_trace_bench(arch: str = "olmo-1b", slots: int = 4,
                      n_requests: int = 16, rate: float = 0.5,
                      sparsity: float = 0.75, seed: int = 0,
                      smoke: bool = True, max_len: int = 64,
                      verbose: bool = True) -> dict:
    """Drive the continuous-batching engine with a seeded Poisson trace.

    Unlike the analytic rows above this *executes* the serving system:
    requests arrive mid-flight, freed slots are reused without a drain
    barrier, and every decode step streams the LM head through the
    bitmap-compressed ``kernels/ops`` path.  Reports measured tok/s and
    p50/p99 request latency — the serving-side analogue of the paper's
    traffic-cut headline.
    """
    from repro.launch.serve import serve_trace

    rep = serve_trace(arch, smoke=smoke, slots=slots, requests=n_requests,
                      rate=rate, max_len=max_len, sparsity=sparsity,
                      seed=seed, verbose=False)
    if verbose:
        lat = rep["latency_s"]
        print(f"  {arch:16s} slots={slots} requests={n_requests} "
              f"rate={rate}/step sparsity={sparsity:.0%}")
        print(f"    {rep['tok_per_s']:8.1f} tok/s | latency "
              f"p50 {lat['p50'] * 1e3:8.1f}ms  p99 {lat['p99'] * 1e3:8.1f}ms"
              f" | occupancy {rep['slot_occupancy']:.0%} | head "
              f"compression {rep['head_compression']:.2f}x")
    return rep


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true",
                    help="run the live continuous-batching engine bench")
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--sparsity", type=float, default=0.75)
    args = ap.parse_args()
    if args.trace:
        serve_trace_bench(args.arch, slots=args.slots,
                          n_requests=args.requests, rate=args.rate,
                          sparsity=args.sparsity)
        return
    print(f"bitmap compression at 75% sparsity (measured, with overhead):"
          f" {measured_compression():.2f}x")
    run()


if __name__ == "__main__":
    main()
