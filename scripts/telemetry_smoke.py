"""CI telemetry smoke: telemetry-on == telemetry-off, artifacts valid.

Runs the same seeded Poisson trace through the engine twice — once with
all three telemetry outputs on, once fully off — and asserts the
observability contract (DESIGN_SERVING.md §Observability):

* served tokens and terminal states are **bit-identical** on vs off
  (telemetry never perturbs scheduling or numerics);
* the profiler trace reads back with one ``serve.step`` span per
  engine step, every ``serve.<phase>`` span nested inside exactly one
  of them without overlap (``read_steps``), and summed phase time covers
  >= ``--min-coverage`` of the summed step wall — the 5 % criterion:
  the phase taxonomy accounts for where step time goes;
* the JSONL event log parses, every record matches the event schema,
  and timestamps are monotonic (``validate_events``);
* the metrics snapshot is valid JSON carrying the registry schema tag.

Run (CI does):
  PYTHONPATH=src python scripts/telemetry_smoke.py --arch olmo-1b
"""
from __future__ import annotations

import argparse
import json
import os
import jax

from repro.serve import ServeEngine, poisson_trace, validate_events
from repro.serve.telemetry import read_steps


def run_once(arch: str, out_dir: str | None, *, requests: int,
             max_len: int, seed: int) -> tuple:
    kw = {}
    if out_dir is not None:
        kw = {"trace_out": os.path.join(out_dir, "trace"),
              "events_out": os.path.join(out_dir, "serve.events.jsonl"),
              "metrics_out": os.path.join(out_dir, "serve.metrics.json")}
    eng = ServeEngine.from_arch(arch, smoke=True, num_slots=2,
                                max_len=max_len, sparsity=0.5,
                                paged=True, page_len=8, prefill_chunk=8,
                                prefix_reuse=True, preempt=True,
                                audit=True, **kw)
    trace = poisson_trace(requests, rate=0.5, seed=seed,
                          vocab_size=eng.cfg.vocab_size,
                          prompt_len=(1, 6), max_new=(2, 6))
    with jax.set_mesh(eng.mesh):
        for spec in trace:
            eng.submit(**spec)
        eng.run()
    eng.close()
    served = [(r.rid, r.state.name, list(r.tokens))
              for r in eng.requests]
    return eng, served


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-coverage", type=float, default=0.95,
                    help="required duration-weighted phase/wall "
                         "coverage floor across the trace")
    ap.add_argument("--out-dir", default="/tmp/repro_telemetry_smoke")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    eng_on, served_on = run_once(args.arch, args.out_dir,
                                 requests=args.requests,
                                 max_len=args.max_len, seed=args.seed)
    eng_off, served_off = run_once(args.arch, None,
                                   requests=args.requests,
                                   max_len=args.max_len, seed=args.seed)
    assert eng_off.telemetry is None and eng_off.spans is None
    assert served_on == served_off, (
        "telemetry-on run diverged from telemetry-off:\n"
        f"on:  {served_on}\noff: {served_off}")
    print(f"tokens bit-identical on vs off "
          f"({sum(len(t) for _, _, t in served_on)} tokens over "
          f"{len(served_on)} requests)")

    steps = read_steps(os.path.join(args.out_dir, "trace"))
    assert len(steps) == eng_on.spans.steps, (
        f"{len(steps)} serve.step spans for {eng_on.spans.steps} steps")
    n_phases = sum(len(s["phases"]) for s in steps)
    cov = (sum(p[2] for s in steps for p in s["phases"])
           / sum(s["dur_ns"] for s in steps))
    assert cov >= args.min_coverage, (
        f"phase coverage {cov} below the {args.min_coverage:.0%} floor "
        f"— the phase taxonomy is leaking step wall time")
    print(f"trace OK: {len(steps)} steps / {n_phases} phase spans, "
          f"phase/wall coverage {cov:.1%}")

    events_path = os.path.join(args.out_dir, "serve.events.jsonl")
    n = validate_events(events_path)
    assert n > 0, "event log is empty"
    print(f"events OK: {n} records, schema + monotonicity hold")

    metrics_path = os.path.join(args.out_dir, "serve.metrics.json")
    with open(metrics_path) as f:
        snap = json.load(f)
    assert snap.get("schema") == "repro.serve.metrics/v1", snap.get(
        "schema")
    assert "step.wall_s" in snap["metrics"], "step histograms missing"
    print(f"metrics OK: {len(snap['metrics'])} metrics in snapshot")
    print(f"telemetry smoke OK (artifacts in {args.out_dir})")


if __name__ == "__main__":
    main()
