"""Shared benchmark utilities: fleet-sweep execution + CI computation + CSV
emission (one file per paper figure, `name,us_per_call,derived` rows for
run.py).

The figure scripts declare :class:`repro.fleet.SweepSpec` grids and execute
them through ``fleet_sweep`` below, which also records each figure's
aggregate indices into ``artifacts/BENCH_fleet.json``.  Env knobs:

  REPRO_FLEET_BACKEND=vmap|sharded|streaming   executor backend (default vmap)
  REPRO_FLEET_CACHE=<dir>   content-addressed result cache: re-runs are free,
                            interrupted streaming sweeps resume per chunk
  REPRO_FLEET_WORKERS=N     dispatch points across N local worker processes
                            (repro.fleet.dispatch; run.py --workers sets it;
                            refused above 1 on a TPU host, where one process
                            holds the chips)
  REPRO_FLEET_LEASE_TTL=S   dispatch lease TTL in seconds (default 30; only
                            a *dead* worker's lease expires — live workers
                            heartbeat-renew — so this is the reclaim delay)
  REPRO_FLEET_PROGRESS=<p>  progress.jsonl path (default artifacts/
                            progress.jsonl; run.py --watch renders it)
  REPRO_FLEET_TRACE=C       per-task telemetry: run every sweep with
                            SwarmConfig.trace_capacity = C (run.py --trace
                            sets it), so BENCH_fleet.json sections gain the
                            task-level indices (task_latency_cdf_s, …)
  REPRO_FLEET_TRACE_HOPS=C  per-hop telemetry: SwarmConfig.trace_hop_capacity
                            = C (run.py --trace-hops sets it) — BENCH
                            sections additionally gain the hop-resolved
                            indices (per-hop transfer-time / link-bits
                            quantiles, queue-wait vs in-flight, airtime-J
                            energy attribution)
  REPRO_FLEET_NEIGHBOR_K=K  sparse neighbor-list path: run sweeps with
                            SwarmConfig.neighbor_mode="sparse",
                            neighbor_k=K (run.py --neighbor-k sets it) —
                            the O(N·k) φ epoch update, DESIGN.md §11
  REPRO_FLEET_TRACE_STATE=E        flight recorder: run every sweep with
                                   SwarmConfig.trace_state_every = E
                                   (run.py --trace-state sets it) — BENCH
                                   sections gain φ-convergence curves,
                                   queue-depth heatmaps, energy-drain
                                   trajectories (DESIGN.md §12)
  REPRO_FLEET_TRACE_STATE_NODES=M  node subsample of the state stream
                                   (first M nodes; 0 = all)
  REPRO_FULL_RUNS=1         the paper's 50 Monte-Carlo runs (default 16)
  REPRO_FLEET_FINGERPRINTS=0   skip the J005 compile-fingerprint table
                               (on by default: tracing is compile-free);
                               REPRO_FLEET_FINGERPRINT_MAX caps points

Multi-host mode: with the ``REPRO_FLEET_*`` rank/world env contract set
(``fleet/dispatch.py``), every figure sweep runs as this rank's worker
against the shared cache; only rank 0 records/returns results.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.chip import check_local_workers, enable_compile_cache
from repro.configs.base import SwarmConfig
from repro.fleet import (ProgressWriter, ResultStore, SweepSpec,
                         build_report, execute, publish_spec, run_sweep,
                         worker_env, write_bench_json)
from repro.fleet.report import ci95  # noqa: F401  (re-export: fig scripts)
from repro.swarm import STRATEGY_NAMES, run_many

ART = os.path.join(os.path.dirname(__file__), "artifacts")
BENCH_JSON = os.path.join(ART, "BENCH_fleet.json")
PROGRESS_JSONL = os.environ.get("REPRO_FLEET_PROGRESS",
                                os.path.join(ART, "progress.jsonl"))

# paper: 50 runs / 95% CI.  The bench default trades Monte-Carlo count for
# wall time on this 1-core container; REPRO_FULL_RUNS=1 restores 50.
DEFAULT_RUNS = 50 if os.environ.get("REPRO_FULL_RUNS") == "1" else 16
DEFAULT_BACKEND = os.environ.get("REPRO_FLEET_BACKEND", "vmap")


def default_store(required: bool = False) -> Optional[ResultStore]:
    """REPRO_FLEET_CACHE store; dispatch needs one (leases + results live
    there), so ``required`` falls back to ``artifacts/fleet_cache``."""
    root = os.environ.get("REPRO_FLEET_CACHE")
    if not root and required:
        root = os.path.join(ART, "fleet_cache")
    return ResultStore(root) if root else None


def default_workers() -> int:
    workers = int(os.environ.get("REPRO_FLEET_WORKERS", "1"))
    check_local_workers(workers)
    return workers


def apply_trace_env(spec: SweepSpec) -> SweepSpec:
    """Fold the ``REPRO_FLEET_TRACE`` / ``REPRO_FLEET_TRACE_HOPS``
    capacities and the ``REPRO_FLEET_NEIGHBOR_K`` sparse-path knob
    (run.py ``--neighbor-k``) into a sweep's base config.

    All three are part of the point identity (they are config fields in
    the digest), so traced/untraced and sparse/dense results never alias
    in the store; with the knobs unset the spec is returned untouched and
    every emitted byte matches the historical build.
    """
    over = {}
    cap = int(os.environ.get("REPRO_FLEET_TRACE", "0"))
    if cap > 0 and spec.base.trace_capacity == 0:
        over["trace_capacity"] = cap
    hop_cap = int(os.environ.get("REPRO_FLEET_TRACE_HOPS", "0"))
    if hop_cap > 0 and spec.base.trace_hop_capacity == 0:
        over["trace_hop_capacity"] = hop_cap
    nk = int(os.environ.get("REPRO_FLEET_NEIGHBOR_K", "0"))
    if nk > 0 and spec.base.neighbor_mode == "dense":
        over["neighbor_mode"] = "sparse"
        over["neighbor_k"] = nk
    se = int(os.environ.get("REPRO_FLEET_TRACE_STATE", "0"))
    if se > 0 and spec.base.trace_state_every == 0:
        over["trace_state_every"] = se
        sn = int(os.environ.get("REPRO_FLEET_TRACE_STATE_NODES", "0"))
        if sn > 0:
            over["trace_state_nodes"] = sn
    if not over:
        return spec
    return dataclasses.replace(
        spec, base=dataclasses.replace(spec.base, **over))


def fleet_sweep(spec: SweepSpec, backend: Optional[str] = None,
                store: Optional[ResultStore] = None,
                record: bool = True,
                workers: Optional[int] = None) -> Dict[str, Dict]:
    """Execute a sweep through the fleet engine: ``{point label: metrics}``.

    Backend/store/workers default from the env knobs above; with ``record``
    the aggregated indices land in ``BENCH_fleet.json`` under
    ``sweep:<spec.name>``.  ``workers > 1`` (or the multi-host env
    contract) routes through ``repro.fleet.dispatch`` — results are
    byte-identical to the single-process path by construction.
    """
    enable_compile_cache()
    backend = backend or DEFAULT_BACKEND
    workers = default_workers() if workers is None else workers
    spec = apply_trace_env(spec)
    env = worker_env()
    if workers > 1 or env.world > 1:
        from repro.fleet.dispatch import DEFAULT_LEASE_TTL_S
        store = store if store is not None else default_store(required=True)
        publish_spec(spec, store)
        res = run_sweep(spec, store, workers=workers, backend=backend,
                        lease_ttl_s=float(os.environ.get(
                            "REPRO_FLEET_LEASE_TTL", DEFAULT_LEASE_TTL_S)),
                        progress_path=PROGRESS_JSONL)
        if res is None:
            return {}    # non-zero rank: computed its share, nothing to emit
    else:
        store = store if store is not None else default_store()
        res = execute(spec, backend=backend, store=store,
                      progress=ProgressWriter(PROGRESS_JSONL))
    if record:
        write_bench_json(
            BENCH_JSON, f"sweep:{spec.name}",
            build_report(res, meta={"backend": backend,
                                    "num_runs": spec.num_runs},
                         # per point: a sweep axis may override either knob
                         tick_s={pt.label: pt.cfg.tick_s
                                 for pt in spec.expand()},
                         tx_power_dbm={pt.label: pt.cfg.tx_power_dbm
                                       for pt in spec.expand()},
                         # per-point config → latency_segments critical-
                         # path attribution on traced points (§14.4)
                         cfg={pt.label: pt.cfg for pt in spec.expand()}))
        fps = _fingerprint_payload(spec)
        if fps:
            from repro.fleet.report import load_bench_json
            merged = dict(load_bench_json(BENCH_JSON).get("fingerprints",
                                                          {}))
            merged[spec.name] = fps
            write_bench_json(BENCH_JSON, "fingerprints", merged)
    return res


def _fingerprint_payload(spec: SweepSpec) -> Dict:
    """J005 compile-fingerprint table of one sweep (DESIGN.md §15.3).

    Tracing is compile-free (``jax.make_jaxpr``, no XLA), so the table is
    cheap next to the sweep itself; still, ``REPRO_FLEET_FINGERPRINTS=0``
    opts out and very large grids are capped (skipped points are counted
    in the payload, never silently dropped).  A tracing failure degrades
    to an ``error`` entry rather than failing the benchmark run: the
    fingerprints section is diagnosis, not a gate on producing numbers.
    """
    if os.environ.get("REPRO_FLEET_FINGERPRINTS", "1") == "0":
        return {}
    cap = int(os.environ.get("REPRO_FLEET_FINGERPRINT_MAX", "64"))
    try:
        from repro.analysis.jaxpr.fingerprint import sweep_fingerprint_table
        return sweep_fingerprint_table(spec, max_points=cap)
    except Exception as e:  # diagnosis must not sink the producer
        return {"sweep": spec.name, "error": f"{type(e).__name__}: {e}"}


def timed_sweep(cfg: SwarmConfig, strategies: Sequence[int], n: int,
                runs: int, key=None) -> Dict[str, Dict]:
    """Legacy per-config strategy sweep over ``run_many`` (kept for the
    ablation scripts; the figure scripts go through ``fleet_sweep``)."""
    key = jax.random.PRNGKey(0) if key is None else key
    out = {}
    for s in strategies:
        t0 = time.perf_counter()
        m = run_many(key, cfg, jnp.int32(s), n, runs)
        m = {k: np.asarray(v) for k, v in m.items()}
        m["_wall_s"] = time.perf_counter() - t0
        out[STRATEGY_NAMES[s]] = m
    return out


def write_csv(path: str, header: str, rows):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    print(f"wrote {path} ({len(rows)} rows)")
