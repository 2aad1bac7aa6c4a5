#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Builds the cell's engine on one device
from the seed, fills it, measures for ``--seconds``, checks what the
timed path served against the plain reference, and prints the result as
one JSON object on the last line of standard output (the end-to-end
metrics with ``--trace 0``, the per-layer metrics with ``--trace 1``).
The numbers compared for ``correct`` are printed beside their limits as
the last lines of standard error and under ``checks`` in the result.

Exits nonzero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when the program cannot be imported.
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
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        from bench import harness, spec
        import repro.serve  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: cannot import the serving stack: {e}",
              file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
