"""Record the J005 compile-fingerprint tables of the paper's figure
sweeps into BENCH_fleet.json — without executing the sweeps.

``jax.make_jaxpr`` traces a point's whole program but compiles nothing,
so fingerprinting the full Fig. 3 / Fig. 5 grids costs seconds where
running them costs minutes.  The tables land in the ``fingerprints``
BENCH section (the same one ``fleet_sweep`` maintains as a side effect of
real runs, benchmarks/common.py), keyed by sweep name: they say *which
point started recompiling* when a sweep's compile time jumps (DESIGN.md
§15.3).

``--check`` turns instability into exit 1: if any same-structural-
signature group of points traces distinct programs, a config field that
should be traced data has leaked into the compiled program — the exact
failure swarmlint J005 exists to catch — and CI fails the day it lands
rather than the day someone notices the sweep got slow.

Usage::

    PYTHONPATH=src:. python benchmarks/fingerprints.py [--check]
"""
from __future__ import annotations

import argparse
import sys

from benchmarks import fig3_gamma, fig5_rate
from benchmarks.common import BENCH_JSON
from repro.analysis.jaxpr.fingerprint import sweep_fingerprint_table
from repro.fleet import write_bench_json
from repro.fleet.report import load_bench_json


def record(specs=None) -> dict:
    """Trace each spec's points and merge the tables into BENCH_fleet.json
    (per-sweep-name merge: tables from real ``fleet_sweep`` runs and from
    this recorder overwrite each other, never accumulate stale keys)."""
    specs = specs if specs is not None else [fig3_gamma.spec(),
                                             fig5_rate.spec()]
    merged = dict(load_bench_json(BENCH_JSON).get("fingerprints", {}))
    tables = {}
    for sp in specs:
        table = sweep_fingerprint_table(sp)
        merged[sp.name] = table
        tables[sp.name] = table
        print(f"fingerprints: {sp.name}: {len(table['points'])} points, "
              f"{table['distinct_programs']} distinct program(s), "
              f"stable={table['stable']}")
    write_bench_json(BENCH_JSON, "fingerprints", merged)
    return tables


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when any same-signature point group "
                         "traces distinct programs (J005 instability)")
    args = ap.parse_args(argv)
    tables = record()
    unstable = {name: t for name, t in tables.items() if not t["stable"]}
    if args.check and unstable:
        for name, t in unstable.items():
            for g in t["unstable_groups"]:
                print(f"fingerprints: UNSTABLE {name}: "
                      f"{', '.join(g['points'])} trace "
                      f"{len(g['programs'])} distinct programs",
                      file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
