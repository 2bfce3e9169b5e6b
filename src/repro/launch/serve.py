"""Serving launcher: φ-partitioned split-computing inference over
heterogeneous executors (the paper's protocol driving a real LM).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --requests 32 --batch 4

On a TPU the model runs at its published widths; on any other backend
its ``reduced`` variant (same code paths, tiny dims).  Sequence lengths
that are multiples of 128 reach the Pallas flash-attention kernel on the
chip; other lengths run the reference attention.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.chip import enable_compile_cache
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.splitcompute import SplitServeEngine, plan_stages


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--executors", type=int, default=4)
    ap.add_argument("--burst", type=int, default=0,
                    help="submit this many extra requests at once to trigger "
                         "the congestion-aware early exit")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if jax.default_backend() != "tpu":
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    # heterogeneous executors (paper Table 2: N(400, 100) GFLOP/s)
    rng = np.random.default_rng(0)
    F = np.maximum(rng.normal(400, 100, args.executors), 50.0)
    plan = plan_stages(cfg, F)
    print("capabilities:", np.round(F, 1).tolist())
    print("φ:", np.round(plan.phi, 1).tolist())
    print("stage boundaries:", plan.boundaries, "executors:", plan.executors)

    eng = SplitServeEngine(cfg, params, plan)
    key = jax.random.PRNGKey(1)
    t0 = time.perf_counter()
    # submit/step share the engine's deterministic epoch clock, so the
    # reported latency is in epoch time (requests × steps), reproducible
    # run-to-run; wall time below is only for throughput
    for _ in range(args.requests):
        key, k = jax.random.split(key)
        toks = jax.random.randint(k, (args.batch, args.seq), 0,
                                  cfg.vocab_size)
        eng.submit({"tokens": toks})
        eng.step()
    for _ in range(args.burst):
        key, k = jax.random.split(key)
        toks = jax.random.randint(k, (args.batch, args.seq), 0,
                                  cfg.vocab_size)
        eng.submit({"tokens": toks})
    stats = eng.drain()
    dt = time.perf_counter() - t0
    print(f"served {stats.completed} sequences in {dt:.2f}s "
          f"({stats.completed / dt:.1f} seq/s), avg latency "
          f"{stats.avg_latency * 1e3:.1f} epoch-ms, "
          f"{len(eng.results)} result tensors stashed")
    print("exit label counts (0=full,1=medium,2=high):", stats.exit_counts)


if __name__ == "__main__":
    main()
