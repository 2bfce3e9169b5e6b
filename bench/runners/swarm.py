"""Runner for ``kind: "swarm"`` configurations: Monte-Carlo batches of the
swarm simulator through the program's own entry point,
``repro.fleet.executor.run_batch``.

One execution is one call of ``run_batch``: ``num_runs`` simulations of
``sim_time_s`` simulated seconds, vmapped on one chip (``backend: vmap``) or
spread over the chips (``backend: sharded``).  Execution *i* of a run with
seed *s* uses the key ``fold_in(PRNGKey(s), i)``.

Set-up compiles the executable (or loads it from the persistent cache) and
warms it up with one execution; the window then runs executions back to
back until ``seconds`` have passed, and ends when its last execution has
returned.  Afterwards, a sample of the window's executions drawn from the
seed is recomputed by the plain reference (``bench/reference/swarm.py``) and
compared statistic by statistic (``bench/compare.py``).
"""
from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.dirname(BENCH) not in sys.path:
    sys.path.insert(0, os.path.dirname(BENCH))

from bench import compare, work  # noqa: E402
from bench.reference import swarm as reference  # noqa: E402

TRACE_LEAD_S = 2.0


def settings(config: Dict, traffic: Dict) -> Dict:
    """The simulator's settings as run: the configuration's, with the
    traffic's arrival parameters over them."""
    s = dict(config["swarm"])
    for k, v in traffic.get("swarm", {}).items():
        if k not in s:
            raise KeyError(f"traffic sets {k!r}, which the configuration "
                           "does not state")
        s[k] = v
    return s


def build_config(s: Dict):
    from repro.configs import SwarmConfig
    return SwarmConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in s.items()})


def execution_keys(seed: int, indices, runs: int):
    """Run keys of the given executions, as ``run_batch`` splits them."""
    import jax
    base = jax.random.PRNGKey(seed)
    return np.concatenate([np.asarray(jax.random.split(
        jax.random.fold_in(base, i), runs)) for i in indices])


def run(ctx: Dict) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.fleet.executor import run_batch

    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    conf, traffic = cell.config, cell.traffic
    s = settings(conf, traffic)
    cfg = build_config(s)
    strategy = reference.STRATEGIES[traffic["strategy"]]
    n, runs = cfg.num_workers, cfg.num_runs
    backend = conf["backend"]
    base = jax.random.PRNGKey(seed)
    events, log = ctx["events"], ctx["log"]

    def execute(i):
        return run_batch(jax.random.fold_in(base, i), cfg,
                         jnp.int32(strategy), n, runs, backend=backend)

    # --- set-up: compile or load, then one warm-up execution --------------
    t0 = time.perf_counter()
    spans: Dict[str, float] = {}
    warm = run_batch(jax.random.fold_in(base, 0), cfg, jnp.int32(strategy),
                     n, runs, backend=backend, spans=spans)
    jax.block_until_ready(warm)
    del warm
    setup = {"compile_s": spans["_compile_s"],
             "warmup_execute_s": spans["_execute_s"],
             "compile_and_warmup_s": time.perf_counter() - t0}

    # --- the window -------------------------------------------------------
    # With tracing, the profiler starts during the tail of execution 0 and
    # stops after execution ``executions``: the trace holds whole
    # executions 1..k and the device's idle time before each of them.  It
    # starts a quarter of an execution (at most TRACE_LEAD_S) before
    # execution 0 is due to end, as the warm-up timed it, since the
    # profiler takes up to a second to start.
    trace_execs = int(conf["trace"]["executions"]) if ctx["trace"] else 0
    timer = None
    if trace_execs:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        warm_s = setup["warmup_execute_s"]
        timer = threading.Timer(
            warm_s - min(TRACE_LEAD_S, 0.25 * warm_s),
            jax.profiler.start_trace, (ctx["trace_dir"],),
            {"profiler_options": opts})
    outs, exec_s, failed = [], [], 0
    compiles_before = events.snapshot()
    t_start = time.perf_counter()
    ctx["window_started"](t_start)
    i, t_end = 0, t_start
    while True:
        t_i = time.perf_counter()
        if timer is not None and i == 0:
            timer.start()
        try:
            with jax.profiler.TraceAnnotation("dispatch", execution=i):
                out = execute(i)
            with jax.profiler.TraceAnnotation("block", execution=i):
                jax.block_until_ready(out)
            t_end = time.perf_counter()
            if timer is not None and i == 0:
                timer.join()
            with jax.profiler.TraceAnnotation("collect", execution=i):
                host = {k: np.asarray(out[k], np.float32)
                        for k in reference.STATS}
            del out
            if not all(np.all(np.isfinite(v)) for v in host.values()):
                failed += 1
            outs.append(host)
        except Exception as e:  # an execution that raises is a failure
            t_end = time.perf_counter()
            log(f"execution {i} raised {type(e).__name__}: {e}")
            failed += 1
            outs.append(None)
        exec_s.append(t_end - t_i)
        if trace_execs and i == trace_execs:
            jax.profiler.stop_trace()
        i += 1
        if t_end - t_start >= seconds and i > trace_execs:
            break
    window_s = t_end - t_start
    attempted = i
    in_window = events.since(compiles_before)

    # --- after the window: memory, then the reference --------------------
    used = jax.devices()[:cell.chips]
    # live buffers plus the memory the runtime reserves for the loaded
    # programs' scratch (the loop state lives there: 6 GB at N = 4096)
    stats = [d.memory_stats() or {} for d in used]
    peak = max(m.get("peak_bytes_in_use", 0)
               + m.get("peak_bytes_reserved", 0) for m in stats)
    sample = compare.sample_executions(
        seed, [k for k, o in enumerate(outs) if o is not None],
        int(conf["compare"]["executions"]))
    t_ref = time.perf_counter()
    got = {k: np.concatenate([outs[j][k] for j in sample])
           for k in reference.STATS} if sample else None
    want = reference_for(cell, seed, sample) if sample else None
    checks = compare.checks(got, want, conf["compare"]["limits"])
    ref_s = time.perf_counter() - t_ref
    correct = failed == 0 and bool(sample) and all(
        c["value"] <= c["limit"] for c in checks.values())

    done = attempted - failed
    info = dict(setup, window_s=window_s, executions=attempted,
                execution_s=exec_s, compiles_in_window=in_window,
                compared_executions=sample, reference_s=ref_s,
                memory_stats=stats)
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": {
            "sim_rate": done * runs * cfg.sim_time_s / window_s,
            "peak_hbm": peak / 1e9,
        },
        "memory_peak_bytes": peak,
        "checks": checks,
        "info": info,
    }
    if trace_execs:
        n_ticks = int(round(cfg.sim_time_s / cfg.decision_period_s)) * \
            int(round(cfg.decision_period_s / cfg.tick_s))
        n_epochs = int(round(cfg.sim_time_s / cfg.decision_period_s))
        per_dev = -(-runs // cell.chips)
        result["counters"] = {
            "ticks": trace_execs * n_ticks,
            "phi_calls": trace_execs * n_epochs,
            "phi_work": work.phi_update(
                per_dev, n, cfg.neighbor_k if cfg.neighbor_mode == "sparse"
                else None),
        }
    return result


def reference_for(cell, seed: int, indices, dtype=None):
    """The reference's statistics for executions ``indices`` of a run with
    ``seed`` (float32 unless ``dtype`` is given: the control)."""
    import jax.numpy as jnp
    s = settings(cell.config, cell.traffic)
    runs = s["num_runs"]
    return reference.run_blocks(
        execution_keys(seed, indices, runs), s, cell.traffic["strategy"],
        int(cell.config["compare"]["block"]), dtype or jnp.float32)

