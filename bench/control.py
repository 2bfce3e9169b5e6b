"""Upper readings for the limits of ``correct``: the control in the
program's place.

    python3 bench/control.py --workload <name> --seeds 7,8,9 \
        [--executions 0,1]

For every seed it computes the reference in the next precision below the
configuration's (bfloat16 for float32) over the run keys of the executions
that a run compares (``--executions``, the window's first ones by
default), compares it with the float32 reference exactly as a run compares
the program, and prints one JSON line with the compared numbers.  The
lower readings are the benchmark's own runs.  Nothing is timed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--executions", type=_ints, default=None)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from bench import compare
    from bench.spec import Cell
    cell = Cell(args.workload)
    runner = cell.runner()
    idx = args.executions if args.executions is not None else \
        list(range(int(cell.config["compare"]["executions"])))
    for seed in args.seeds:
        want = runner.reference_for(cell, seed, idx)
        got = runner.reference_for(cell, seed, idx, jnp.bfloat16)
        print(json.dumps({
            "reading": "control", "workload": cell.name, "seed": seed,
            "executions": idx,
            "checks": compare.checks(got, want,
                                     cell.config["compare"]["limits"]),
            "gaps": compare.stat_gaps(got, want)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
