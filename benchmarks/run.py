"""Benchmark entry point — one function per paper table/figure.

Prints the figure reproductions (Fig. 3-7) and the roofline table from the
dry-run artifacts.  Env knobs:
  REPRO_FULL_RUNS=1   use the paper's 50 Monte-Carlo runs (default 16)
  REPRO_BENCH_FAST=1  tiny sweep for CI smoke (2 runs)

Flags:
  --workers N   dispatch every fleet sweep across N local worker processes
                (``repro.fleet.dispatch``; results byte-identical to N=1)
  --trace [C]   run every fleet sweep with per-task telemetry
                (``SwarmConfig.trace_capacity = C``, default 65536): each
                sweep's BENCH_fleet.json section gains the task-level
                indices (``task_latency_cdf_s``, hop/exit histograms,
                energy per task) computed from in-scan TaskRecords, and a
                trace-driven figure pass (``fig_trace``) emits the
                Fig. 4a per-task CDF overlay CSV
  --trace-hops [C]  additionally capture the per-hop stream
                (``SwarmConfig.trace_hop_capacity = C``, default 65536):
                BENCH sections gain hop-resolved indices (per-hop
                transfer-time / link-bits quantiles, queue-wait vs
                in-flight decomposition)
  --neighbor-k K  run every fleet sweep on the sparse neighbor-list path
                (``SwarmConfig.neighbor_mode="sparse"``, ``neighbor_k=K``):
                the O(N·k) φ epoch update instead of the dense [N, N] one
  --trace-state [E]  flight recorder: run every fleet sweep with the
                per-epoch swarm-state stream on
                (``SwarmConfig.trace_state_every = E``, default stride 1):
                BENCH sections gain φ-convergence, queue-heatmap and
                energy-drain indices, and a state-driven figure pass
                (``fig_state``) emits the φ-convergence + queue-heatmap
                CSVs; while sweeps run, workers append per-point system
                gauges to progress.jsonl (``--watch`` renders swarm health)
  --watch [p]   don't run benchmarks: follow a progress.jsonl (default
                ``artifacts/progress.jsonl``) and render completed/total,
                points/min, ETA and — when the flight recorder is on —
                the live swarm gauges (mean/max queue depth, φ spread,
                completion rate) for the sweep currently running —
                locally or on any host sharing the progress file.
                ``benchmarks/loadtest.py`` (the open-loop SLO knee sweep,
                DESIGN.md §14) streams its gauges — p50/p99 latency,
                goodput, drop rate — onto the same surface.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

FAST = os.environ.get("REPRO_BENCH_FAST") == "1"


def watch(path: str, interval: float = 2.0) -> None:
    """Render live sweep progress from a shared progress.jsonl."""
    from repro.fleet import progress_summary, read_progress, render_progress
    last = None
    while True:
        s = progress_summary(read_progress(path))
        line = render_progress(s)
        if line != last:
            print(line, flush=True)
            last = line
        if s is not None and s["total"] > 0 and s["completed"] >= s["total"]:
            return
        time.sleep(interval)


def run_benchmarks() -> None:
    from benchmarks import (fig3_gamma, fig4_workers, fig5_rate, fig6_area,
                            fig7_earlyexit, roofline)
    from repro.fleet import worker_env

    # fleet sweeps coordinate across ranks through the shared store, but
    # the ablation/roofline producers don't — running them on every rank
    # would race the read-modify-write of BENCH_fleet.json; rank 0 owns
    # them
    rank0 = worker_env().rank == 0

    kw = {"runs": 2} if FAST else {}

    print("\n== Fig. 3: gamma sensitivity ==")
    fig3_gamma.run(gammas=(0.02, 0.1) if FAST else
                   (0.002, 0.01, 0.02, 0.05, 0.1, 0.3), **kw)
    print("\n== Fig. 4: workers sweep ==")
    fig4_workers.run(workers=(10, 30) if FAST else (10, 20, 30, 40, 50),
                     **kw)
    print("\n== Fig. 5: arrival rate ==")
    fig5_rate.run(periods_ms=(60, 100) if FAST else (60, 70, 80, 90, 100),
                  **kw)
    print("\n== Fig. 6: mission area ==")
    fig6_area.run(areas_km=(20, 40) if FAST else (10, 20, 30, 40), **kw)
    print("\n== Fig. 7: early exit ==")
    fig7_earlyexit.run(workers=(10, 30) if FAST else (10, 20, 30, 40, 50),
                       **kw)

    print("\n== Scenario sweep (ours): mobility x channel x churn ==")
    from benchmarks import fig_scenarios
    fig_scenarios.run(scenarios=fig_scenarios.SCENARIOS[:3] if FAST
                      else fig_scenarios.SCENARIOS,
                      sim_time=10.0 if FAST else 20.0, **kw)

    if int(os.environ.get("REPRO_FLEET_TRACE", "0")) > 0:
        print("\n== Trace-driven figures: Fig. 4a per-task CDF overlay ==")
        from benchmarks import fig_trace
        fig_trace.run(n=10 if FAST else 30,
                      strategies=(0, 4) if FAST else (0, 1, 2, 3, 4),
                      sim_time=5.0 if FAST else None, **kw)

    if int(os.environ.get("REPRO_FLEET_TRACE_STATE", "0")) > 0:
        print("\n== State-driven figures: φ-convergence + queue heatmap ==")
        from benchmarks import fig_state
        fig_state.run(n=10 if FAST else 30,
                      strategies=(0, 4) if FAST else (0, 1, 2, 3, 4),
                      sim_time=5.0 if FAST else None, **kw)

    if rank0:
        print("\n== Ablation (ours): arrival burstiness ==")
        from benchmarks import ablation_burst
        ablation_burst.run(duties=(0.25, 1.0) if FAST else
                           (0.125, 0.25, 0.5, 1.0), **kw)

        print("\n== Roofline (from dry-run artifacts) ==")
        roofline.run()


def main(argv=None) -> None:
    from benchmarks.common import PROGRESS_JSONL
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="dispatch fleet sweeps across N local worker "
                         "processes (repro.fleet.dispatch)")
    ap.add_argument("--trace", nargs="?", const=65536, default=None,
                    type=int, metavar="CAPACITY",
                    help="per-task telemetry: run sweeps with "
                         "SwarmConfig.trace_capacity=CAPACITY (default "
                         "65536) so BENCH sections gain task-level CDFs, "
                         "and emit the Fig. 4a overlay CSV (fig_trace)")
    ap.add_argument("--trace-hops", nargs="?", const=65536, default=None,
                    type=int, metavar="CAPACITY",
                    help="per-hop telemetry: SwarmConfig.trace_hop_capacity"
                         "=CAPACITY (default 65536) — BENCH sections gain "
                         "hop-resolved transfer indices")
    ap.add_argument("--neighbor-k", type=int, default=None, metavar="K",
                    help="run every fleet sweep on the sparse neighbor-list "
                         "path (SwarmConfig.neighbor_mode='sparse', "
                         "neighbor_k=K) — the O(N·k) φ epoch update")
    ap.add_argument("--trace-state", nargs="?", const=1, default=None,
                    type=int, metavar="EVERY",
                    help="flight recorder: SwarmConfig.trace_state_every="
                         "EVERY (default stride 1) — BENCH sections gain "
                         "φ-convergence / queue-heatmap / energy-drain "
                         "indices and fig_state emits the state CSVs")
    ap.add_argument("--watch", nargs="?", const=PROGRESS_JSONL, default=None,
                    metavar="PROGRESS_JSONL",
                    help="follow a progress file instead of running "
                         f"benchmarks (default {PROGRESS_JSONL})")
    args = ap.parse_args(argv)

    if args.watch is not None:
        watch(args.watch)
        return
    from repro.chip import check_local_workers, enable_compile_cache
    enable_compile_cache()
    if args.workers is not None:
        check_local_workers(args.workers)
        # common.fleet_sweep reads the knob at call time, so setting the
        # env here covers every figure sweep below
        os.environ["REPRO_FLEET_WORKERS"] = str(args.workers)
    if args.trace is not None:
        os.environ["REPRO_FLEET_TRACE"] = str(args.trace)
    if args.trace_hops is not None:
        os.environ["REPRO_FLEET_TRACE_HOPS"] = str(args.trace_hops)
    if args.neighbor_k is not None:
        os.environ["REPRO_FLEET_NEIGHBOR_K"] = str(args.neighbor_k)
    if args.trace_state is not None:
        os.environ["REPRO_FLEET_TRACE_STATE"] = str(args.trace_state)
    run_benchmarks()


if __name__ == "__main__":
    main()
