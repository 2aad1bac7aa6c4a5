"""SPMD serving smoke: sharded packed serving on 8 fake devices.

Forces an 8-device host platform (``--xla_force_host_platform_device_
count=8`` — set before jax imports, so run this script directly), then
serves the same Poisson-ish trace twice on the elastic (data, model)
mesh — once replicated (``mp=1``, KV data-sharded 8 ways) and once
tensor-parallel (``mp=4``, weights column/row-sharded, KV data-sharded
2 ways) — and gates on the hard guarantees:

* tokens are bit-identical between the two topologies (greedy and
  temperature-sampled requests alike);
* zero fallbacks blamed on ``model_parallel`` (those paths are gone)
  and zero per-tensor shard fallbacks on the smoke shapes;
* the per-device weight stream: summed over sharded manifest entries
  the per-device packed bytes are the totals floor-divided by mp.

Exit status is the CI contract: non-zero on any violated guarantee.

  PYTHONPATH=src python scripts/spmd_smoke.py --arch olmo-1b --mp 4
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import warnings


def _run(arch: str, mp: int, sparsity: float, requests: int,
         max_len: int):
    from repro.configs import get_smoke_config
    from repro.serve import ServeEngine

    cfg = get_smoke_config(arch)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # e.g. granite's dense head
        eng = ServeEngine(cfg, num_slots=8, max_len=max_len,
                          sparsity=sparsity, model_parallel=mp, seed=0,
                          paged=True, page_len=8, prefill_chunk=8,
                          prefix_reuse=True, preempt=True)
    prompts = [[1 + (i * 7 + j) % 250 for j in range(5 + i % 4)]
               for i in range(requests)]
    reqs = [eng.submit(p, max_new_tokens=6, arrival=float(i // 2),
                       temperature=(0.8 if i % 2 else 0.0), seed=100 + i,
                       top_k=(8 if i % 2 else None))
            for i, p in enumerate(prompts)]
    rep = eng.run()
    eng.kv.audit()
    return eng, rep, {r.rid: list(r.tokens) for r in reqs}


def _gate_weight_stream(eng, rep, mp: int) -> None:
    ws = rep["weight_stream"]
    assert ws["shards"] == mp, (ws["shards"], mp)
    assert ws["shard_fallbacks"] == {}, ws["shard_fallbacks"]
    for key, reason in rep["fallbacks"].items():
        assert "model_parallel" not in reason, (key, reason)
    # per-device packed bytes == totals / mp, floor-div per tensor
    dev = tot = n = 0
    for e in eng.packed.manifest:
        if e.shard is not None:
            n += 1
            tot += int(e.sparse_bytes)
            dev += int(e.sparse_bytes) // e.shard[1]
    if mp > 1:
        assert n > 0, "nothing sharded at mp>1"
        assert dev * mp <= tot < dev * mp + mp * n, (dev, tot, n)
    else:
        assert dev == tot


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--mp", type=int, default=4)
    ap.add_argument("--sparsity", type=float, default=0.75)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-len", type=int, default=48)
    args = ap.parse_args()

    import jax
    ndev = jax.device_count()
    assert ndev % args.mp == 0, (ndev, args.mp)

    eng1, rep1, base = _run(args.arch, 1, args.sparsity, args.requests,
                            args.max_len)
    engN, repN, toks = _run(args.arch, args.mp, args.sparsity,
                            args.requests, args.max_len)
    assert engN._spmd and eng1._spmd
    assert dict(engN.mesh.shape) == {"data": ndev // args.mp,
                                     "model": args.mp}
    assert toks == base, "mp=%d tokens diverged from mp=1" % args.mp
    _gate_weight_stream(eng1, rep1, 1)
    _gate_weight_stream(engN, repN, args.mp)

    ws = repN["weight_stream"]
    print(f"[{args.arch}] mesh {dict(engN.mesh.shape)}: "
          f"{args.requests} requests bit-identical mp=1 vs mp={args.mp}, "
          f"kv shards {eng1.kv.shards}->{engN.kv.shards}, per-device "
          f"sparse {ws['device_sparse_bytes_per_step']}B of "
          f"{ws['sparse_bytes_per_step']}B/step, zero shard fallbacks")
    print("spmd smoke OK")


if __name__ == "__main__":
    main()
