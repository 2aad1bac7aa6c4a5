#!/usr/bin/env python3
"""Read the compared number of the program and of its precision control
over several seeds, in one process, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51 \
        [--traced N]

Each seed is a whole run of the cell (the same set-up, window and sample
as ``bench/run.py``) that, after the window, computes both the reference
and the control: the reference with every matrix product's operands
rounded to float8 (``compare.CONTROL_DTYPE``) put in the program's
place.  Prints one JSON line per seed with each compared number for the
program and for the control, and whether each is correct by the cell's
limits, then a summary: for each number the largest program reading and
the smallest control reading, the two readings a limit is set between.
The benchmark's own runs never compute the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from bench import entry  # noqa: E402


def main() -> int:
    entry.prepare()
    from bench import harness, spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", type=int, default=0,
                    help="run the first N seeds with --trace 1")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    rows = []
    t = T_START
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, i < args.traced, t,
                          control=True)
        lim = {k: c["limit"] for k, c in out["checks"].items()}
        got = out["readings"]
        row = {"seed": seed, "attempted": out["attempted"],
               "failed": out["failed"], "program": got["program"],
               "control": got["control"],
               "program_correct": all(got["program"][k] <= v
                                      for k, v in lim.items()),
               "control_correct": out["correct"], "limits": lim,
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "device": out["device"]}
        if "breakdown" in out:
            row["breakdown"] = out["breakdown"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        t = time.perf_counter()
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows)
                        for k in rows[0]["program"]},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in rows[0]["control"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
