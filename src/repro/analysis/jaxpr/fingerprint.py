"""J005 — compile-fingerprint stability (DESIGN.md §15.3).

The fleet executors compile one program per ``(cfg, n)`` pair; the whole
sweep-economics story (DESIGN.md §8) assumes that *data-like* config
changes — gamma, arrival rates, channel parameters — retrace to the
**same** program, because the python floats fold into literals whose
values never reach program *structure*.  A "leaked static arg" breaks
that silently: a python-level branch on a float, a shape derived from a
parameter, a host-side rounding — and suddenly every grid cell of a
sweep compiles its own executable.  A sweep sees the compile-time
cliff but cannot say *which point* started recompiling.

This module makes the contract checkable:

* :func:`program_fingerprint` — sha256 of a *canonicalized* jaxpr:
  variables renamed by first appearance, literal and constant **values**
  abstracted to their avals (so data differences vanish), sub-jaxprs
  recursed, structural params (scan ``length``, branch count, …) kept
  verbatim.  Two traces share a fingerprint iff they are the same
  program shape.
* :func:`structural_signature` — splits a :class:`SweepPoint` into the
  fields that *legitimately* change the program (n, num_runs, every
  non-float config field, and the float fields that set scan lengths)
  versus the data-like rest.
* :func:`sweep_fingerprint_table` — per-point fingerprints + stability
  verdict for a sweep, emitted into ``BENCH_fleet.json`` so the perf
  gate can name the offending point by label.
* :func:`check_j005` — the repo-level rule: expand stand-in data-only
  sweeps over the real ``run_sim`` and fail if any same-signature group
  traces more than one distinct program.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

from repro.analysis.astutil import Finding
from repro.analysis.jaxpr.jaxpr_util import HAVE_JAX

#: float config fields that legitimately change program structure: they
#: set the epoch/tick scan lengths (python-computed trip counts)
STRUCTURAL_FLOATS = frozenset({"sim_time_s", "decision_period_s", "tick_s"})

#: hex digits shown in tables / finding messages (full digest in hashes)
SHORT = 12


# --------------------------------------------------------------------------
# canonical jaxpr hashing
# --------------------------------------------------------------------------


def _canon_value(val, lines: List[str]) -> str:
    """Canonical token for one param value: recurse jaxprs, abstract
    array values to avals, keep scalars/strings verbatim (they are
    structural: scan lengths, dimension numbers, modes …)."""
    closed = getattr(val, "jaxpr", None)
    if closed is not None and hasattr(closed, "eqns"):      # ClosedJaxpr
        return "jaxpr{" + _canon_jaxpr(closed) + "}"
    if hasattr(val, "eqns"):                                # raw Jaxpr
        return "jaxpr{" + _canon_jaxpr(val) + "}"
    if isinstance(val, (tuple, list)):
        return "(" + ",".join(_canon_value(v, lines) for v in val) + ")"
    if hasattr(val, "shape") and hasattr(val, "dtype"):     # array const
        return f"arr[{val.dtype}{tuple(val.shape)}]"
    if callable(val):
        # callables in params (custom_jvp rules, …) are identified by
        # qualname only — identity would defeat cross-trace comparison
        return f"fn:{getattr(val, '__qualname__', repr(type(val)))}"
    return repr(val)


def _canon_jaxpr(jaxpr) -> str:
    """Render a jaxpr with first-appearance variable numbering and
    value-abstracted literals/consts; the digest input for fingerprints."""
    names: Dict[int, str] = {}

    def nm(v) -> str:
        if hasattr(v, "val"):                               # Literal
            return f"lit[{v.aval.str_short()}]"
        key = id(v)
        if key not in names:
            names[key] = f"v{len(names)}"
        return f"{names[key]}:{v.aval.str_short()}"

    lines: List[str] = []
    lines.append("in=" + ",".join(nm(v) for v in jaxpr.constvars))
    lines.append("arg=" + ",".join(nm(v) for v in jaxpr.invars))
    for eqn in jaxpr.eqns:
        params = ",".join(
            f"{k}={_canon_value(v, lines)}"
            for k, v in sorted(eqn.params.items()))
        lines.append(
            f"{eqn.primitive.name}[{params}]"
            f"({','.join(nm(v) for v in eqn.invars)})"
            f"->({','.join(nm(v) for v in eqn.outvars)})")
    lines.append("out=" + ",".join(nm(v) for v in jaxpr.outvars))
    return "\n".join(lines)


def program_fingerprint(closed_jaxpr) -> str:
    """sha256 hex digest of the canonicalized program."""
    text = _canon_jaxpr(closed_jaxpr.jaxpr)
    return hashlib.sha256(text.encode()).hexdigest()


def fingerprint_fn(fn, *args) -> str:
    """Trace ``fn(*args)`` and fingerprint the program."""
    import jax
    return program_fingerprint(jax.make_jaxpr(fn)(*args))


# --------------------------------------------------------------------------
# sweep-point fingerprints
# --------------------------------------------------------------------------


def structural_signature(point) -> Tuple[Tuple[str, Any], ...]:
    """The fields of a SweepPoint that may legitimately move the
    fingerprint.  Strategy is deliberately *excluded*: the executors keep
    it traced (an i32 argument), so two points differing only in strategy
    must share a program — grouping them together makes J005 catch a
    strategy that leaks to static."""
    cfg = point.cfg
    sig: List[Tuple[str, Any]] = [("n", point.n),
                                  ("num_runs", point.num_runs)]
    for f in dataclasses.fields(type(cfg)):
        val = getattr(cfg, f.name)
        if not isinstance(val, float) or f.name in STRUCTURAL_FLOATS:
            sig.append((f.name, val))
    return tuple(sig)


def point_fingerprint(point) -> str:
    """Fingerprint the single-run simulator program of one sweep point —
    the unit every executor backend batches (vmap/stream/shard all wrap
    this same trace, so its stability is theirs)."""
    import jax
    import jax.numpy as jnp

    from repro.swarm.simulator import run_sim
    cfg, n = point.cfg, point.n

    def fn(key, strategy):
        return run_sim(key, cfg, strategy, n)
    return fingerprint_fn(fn, jax.random.PRNGKey(0), jnp.int32(0))


def group_fingerprints(labeled: Iterable[Tuple[Any, str, Any]]
                       ) -> List[Dict[str, Any]]:
    """Group (signature, label, fingerprint) rows; one dict per
    signature group with its distinct fingerprints and a verdict."""
    groups: Dict[Any, Dict[str, Any]] = {}
    for sig, label, fp in labeled:
        g = groups.setdefault(sig, {"labels": [], "fingerprints": {}})
        g["labels"].append(label)
        g["fingerprints"].setdefault(fp, []).append(label)
    out = []
    for sig, g in groups.items():
        out.append({
            "signature": dict(sig) if isinstance(sig, tuple) else sig,
            "points": g["labels"],
            "distinct_programs": len(g["fingerprints"]),
            "stable": len(g["fingerprints"]) <= 1,
            "programs": {fp[:SHORT]: labels
                         for fp, labels in g["fingerprints"].items()},
        })
    return out


def sweep_fingerprint_table(spec, max_points: Optional[int] = None
                            ) -> Dict[str, Any]:
    """Fingerprint every point of a sweep; the dict lands under
    ``fingerprints:<sweep>`` in BENCH_fleet.json (benchmarks/common.py)
    and names which point started recompiling.

    ``max_points`` caps tracing cost for very large grids (points beyond
    the cap are reported as skipped, never silently dropped).
    """
    points = spec.expand()
    skipped = 0
    if max_points is not None and len(points) > max_points:
        skipped = len(points) - max_points
        points = points[:max_points]
    rows = []
    table: Dict[str, str] = {}
    for p in points:
        fp = point_fingerprint(p)
        table[p.label] = fp[:SHORT]
        rows.append((structural_signature(p), p.label, fp))
    groups = group_fingerprints(rows)
    return {
        "sweep": spec.name,
        "points": table,
        "groups": groups,
        "distinct_programs": len(set(table.values())),
        "unstable_groups": [g for g in groups if not g["stable"]],
        "skipped_points": skipped,
        "stable": all(g["stable"] for g in groups),
    }


# --------------------------------------------------------------------------
# the repo-level rule
# --------------------------------------------------------------------------


def _standin_specs():
    """Data-only sweeps over the real simulator: every axis below moves
    floats that must **not** move the program.  Small n / short sim keeps
    the traces cheap; fingerprints do not depend on array sizes."""
    from repro.configs.base import SwarmConfig
    from repro.fleet.sweep import SweepSpec
    base = SwarmConfig(num_workers=13, sim_time_s=1.0, num_runs=2)
    sparse = dataclasses.replace(base, neighbor_mode="sparse", neighbor_k=4)
    return [
        SweepSpec.build("j005_gamma", base,
                        axes={"gamma": (0.01, 0.02, 0.05)},
                        strategies=(0, 4), num_runs=2),
        SweepSpec.build("j005_load", base,
                        axes={"task_period_s": (0.03, 0.06),
                              "tx_power_dbm": (24.0, 30.0)},
                        strategies=(4,), num_runs=2),
        SweepSpec.build("j005_sparse_gamma", sparse,
                        axes={"gamma": (0.01, 0.05)},
                        strategies=(4,), num_runs=2),
    ]


def check_j005(traced, root: str) -> Iterable[Finding]:
    """J005: points differing only in data must trace identical programs.

    ``traced`` (the shared target map) is unused — this rule traces its
    own stand-in sweeps because the hazard lives in the *sweep grid*,
    not in any single target; same signature for registry uniformity."""
    del traced, root
    if not HAVE_JAX:                                 # pragma: no cover
        return
    sfile = "src/repro/fleet/sweep.py"
    for spec in _standin_specs():
        table = sweep_fingerprint_table(spec)
        for g in table["unstable_groups"]:
            programs = "; ".join(
                f"{fp}: {', '.join(labels[:3])}"
                f"{'…' if len(labels) > 3 else ''}"
                for fp, labels in g["programs"].items())
            yield Finding(
                "J005", sfile, 0, f"sweep:{spec.name}",
                f"compile-fingerprint instability: {g['distinct_programs']}"
                f" distinct programs in one structural-signature group of "
                f"stand-in sweep '{spec.name}' ({programs}) — a data-like "
                f"config field is leaking into program structure, so this "
                f"grid recompiles per point")
