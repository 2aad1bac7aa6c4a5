"""Chaos smoke: the full seeded fault schedule under audit, per arch.

Runs ``FaultPlan.chaos(seed)`` against the full serving stack (paged KV
+ prefix reuse + preemption + chunked prefill) with ``audit=True`` and
asserts the hard guarantees the fault-injection harness exists to
enforce:

* every submitted request terminates in a typed terminal state;
* zero invariant-audit violations across every step;
* zero page leaks after drain (refcount conservation holds);
* the served tokens are *bit-identical* to a never-faulted run —
  corruption quarantines to dense (packing is lossless) and preempted
  requests replay deterministically.

Exit status is the CI contract: non-zero on any violated guarantee.

  PYTHONPATH=src python scripts/chaos_smoke.py --archs olmo-1b gemma3-4b
"""
from __future__ import annotations

import argparse
import warnings
import jax

from repro.configs import get_smoke_config
from repro.serve import FaultPlan, RequestState, ServeEngine, poisson_trace


def _run(cfg, seed: int, faults=None, audit: bool = False):
    eng = ServeEngine(cfg, num_slots=2, max_len=64, sparsity=0.5,
                      seed=seed, paged=True, page_len=8,
                      prefix_reuse=True, preempt=True, prefill_chunk=4,
                      audit=audit, faults=faults)
    trace = poisson_trace(8, rate=0.5, seed=seed,
                          vocab_size=eng.cfg.vocab_size,
                          prompt_len=(1, 6), max_new=(4, 10))
    with jax.set_mesh(eng.mesh):
        reqs = [eng.submit(**spec) for spec in trace]
        rep = eng.run()
    return eng, rep, {r.rid: list(r.tokens) for r in reqs}


def chaos_smoke(arch: str, seed: int) -> None:
    cfg = get_smoke_config(arch)
    _, _, clean = _run(cfg, seed)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # quarantine warnings expected
        eng, rep, toks = _run(cfg, seed, audit=True,
                              faults=FaultPlan.chaos(seed=seed))
    fs = rep["lifecycle"]["faults"]
    assert fs["fired"] >= 3, f"chaos plan barely fired: {fs['log']}"
    for r in eng.requests:
        assert r.state in (RequestState.DONE,), \
            f"rid {r.rid} ended {r.state.name}"
    assert toks == clean, "faulted tokens diverged from the clean run"
    eng.kv.flush_prefix()
    eng.kv.audit()
    for pool in eng.kv.pools.values():
        assert not pool.ref and not pool.held, "page leak"
    au = rep["lifecycle"]["audit"]
    print(f"[{arch}] {fs['fired']}/{fs['planned']} faults fired "
          f"(seed {seed}), {au['steps_checked']} steps audited, "
          f"{len(rep['lifecycle']['quarantined'])} tensors quarantined, "
          f"tokens bit-identical, zero leaks")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+", default=["olmo-1b", "gemma3-4b"])
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args()
    for arch in args.archs:
        chaos_smoke(arch, args.seed)
    print("chaos smoke OK")


if __name__ == "__main__":
    main()
