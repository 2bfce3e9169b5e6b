"""Smoke run of the main paths on a TPU: the swarm simulator and the
split-serving engine, through their normal entry points at real sizes.

    python3 chip_smoke.py              # one chip: phases a, b, c
    python3 chip_smoke.py --chips 4    # four chips: sharded vs vmap only

Phases (one process; weights and swarms are random from ``--seed``):

  a. Paper swarm: ``SwarmConfig()`` (Table 2: N=30, 20 km, 100 s, 50 runs,
     dense) through ``fleet.run_batch(backend="vmap")`` for Distributed and
     LocalOnly.  The compiled program must hold the φ Pallas kernel, the
     metrics must be sane, and ``phi_update_op`` must match ``phi_update``
     at N=30 and N=1024.
  b. Large sparse swarm: ``neighbor_mode="sparse"`` at N=4096, area side
     scaled by sqrt(N/30) to keep Table 2's density, 2 runs, simulated time
     cut to fit (printed).  ``phi_update_op`` over neighbour lists (the
     sparse kernel) must match ``phi_update_sparse``, and sparse must
     equal dense bit for bit at a small N where K covers every degree.
  c. Split serving: qwen3-1.7b at its published widths through
     ``plan_stages`` + ``SplitServeEngine`` (4 executors, batch 4, seq
     128).  One request's logits must match an unsplit forward pass; the
     attention kernels must match their references at these widths.

``--chips 4`` runs only phase a's point on the ``sharded`` backend over
four chips and requires it to equal ``vmap`` on one chip bit for bit.

The seconds printed are smoke timings of one cold run (compile included
where said), not benchmark metrics.  The last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed on a TPU; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.chip import enable_compile_cache  # noqa: E402
from repro.configs import SwarmConfig, get_config  # noqa: E402
from repro.core.diffusive import (phi_update, phi_update_op,  # noqa: E402
                                  phi_update_sparse)
from repro.core.neighborhood import Neighborhood  # noqa: E402
from repro.fleet import executor, run_batch  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.splitcompute import SplitServeEngine, plan_stages  # noqa: E402
from repro.swarm import DISTRIBUTED, LOCAL_ONLY, STRATEGY_NAMES  # noqa: E402
from repro.trace import schema  # noqa: E402

# f32 parity between the Pallas kernels and the jnp references: the same
# arithmetic, but each side's divide is its own compiler's
F32_RTOL = 1e-5
# bf16 compute (the models' compute dtype)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def log(msg: str) -> None:
    print(msg, flush=True)


def has_kernel(compiled) -> bool:
    """Whether an executable holds a Pallas TPU kernel."""
    return "tpu_custom_call" in compiled.as_text()


def check_metrics(m, tag: str) -> None:
    m = {k: np.asarray(v) for k, v in m.items()}
    for k, v in m.items():
        if not np.all(np.isfinite(v)):
            raise AssertionError(f"{tag}: non-finite {k}: {v}")
    if not np.all(m["completed"] <= m["generated"]):
        raise AssertionError(f"{tag}: completed > generated")
    if not np.all(m["completed"] > 0):
        raise AssertionError(f"{tag}: a run completed no task")
    log(f"[{tag}] completed/run mean {m['completed'].mean()} of generated "
        f"{m['generated'].mean()}, avg latency {m['avg_latency_s'].mean()} s")


def check_close(got, want, tag: str, rtol: float, atol: float = 0.0):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.max(np.abs(got - want) / (atol + rtol * np.abs(want) + 1e-30))
    log(f"[{tag}] max |got - want| = {np.max(np.abs(got - want))} "
        f"({err} of the tolerance)")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=tag)


def random_graph(key, n: int):
    """φ, F, a symmetric adjacency and link delays like the simulator's."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    F = jax.random.uniform(k1, (n,), jnp.float32, 100, 700)
    phi = jax.random.uniform(k2, (n,), jnp.float32, 50, 900)
    a = jax.random.bernoulli(k3, min(1.0, 16.0 / n), (n, n))
    adj = (a | a.T) & ~jnp.eye(n, dtype=bool)
    d_tx = jax.random.uniform(k4, (n, n), jnp.float32, 1e-4, 1e-2)
    return phi, F, adj, d_tx


def timed_batch(key, cfg, strategy, n, runs, backend="vmap"):
    spans = {}
    out = run_batch(key, cfg, jnp.int32(strategy), n, runs, backend=backend,
                    spans=spans)
    out = {k: np.asarray(v) for k, v in out.items()}
    return out, spans


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_paper(seed: int, cfg: SwarmConfig, parity_ns=(30, 1024)) -> None:
    key = jax.random.PRNGKey(seed)
    n, runs = cfg.num_workers, cfg.num_runs
    for s in (DISTRIBUTED, LOCAL_ONLY):
        m, spans = timed_batch(key, cfg, s, n, runs)
        log(f"[a] smoke timing, {STRATEGY_NAMES[s]} N={n} x {runs} runs, "
            f"{cfg.sim_time_s} s simulated: compile {spans['_compile_s']} s "
            f"(one executable for both strategies), execute "
            f"{spans['_execute_s']} s")
        check_metrics(m, f"a:{STRATEGY_NAMES[s]}")
    compiled, _ = executor._profiled_vmap(cfg, n, runs)
    if not has_kernel(compiled):
        raise AssertionError("a: the simulator program holds no φ kernel")
    log("[a] the compiled simulator holds the φ Pallas kernel")
    for i, N in enumerate(parity_ns):
        phi, F, adj, d_tx = random_graph(jax.random.fold_in(key, i), N)
        got = jax.jit(phi_update_op)(phi, F, Neighborhood(adj), d_tx)
        want = jax.jit(phi_update)(phi, F, adj, d_tx)
        check_close(got, want, f"a:phi_update_op vs phi_update N={N}",
                    F32_RTOL)


def phase_sparse(seed: int, n: int = 4096, runs: int = 2,
                 sim_time_s: float = 20.0, small_n: int = 64,
                 small_runs: int = 4) -> None:
    key = jax.random.PRNGKey(seed)
    paper = SwarmConfig()
    cfg = dataclasses.replace(
        paper, num_workers=n, num_runs=runs, neighbor_mode="sparse",
        area_m=paper.area_m * math.sqrt(n / paper.num_workers),
        sim_time_s=sim_time_s)
    log(f"[b] cut: simulated time {sim_time_s} s of Table 2's "
        f"{paper.sim_time_s} s, {runs} runs of {paper.num_runs}, to fit the "
        f"run's time and one chip's memory; area side {cfg.area_m} m "
        f"(Table 2's density at N={n}), K={cfg.neighbor_k}")
    m, spans = timed_batch(key, cfg, DISTRIBUTED, n, runs)
    log(f"[b] smoke timing, Distributed sparse N={n} x {runs} runs: compile "
        f"{spans['_compile_s']} s, execute {spans['_execute_s']} s")
    check_metrics(m, "b:sparse")

    # the sparse kernel against its reference on the same neighbor lists
    from repro.swarm import neighbor_lists
    from repro.swarm.channel import link_state_sparse
    kp, kf = jax.random.split(jax.random.fold_in(key, 1))
    pos = jax.random.uniform(kp, (n, 2), jnp.float32, 0.0, cfg.area_m)
    nbr, valid = neighbor_lists(pos, cfg)
    adj_e, cap_e = link_state_sparse(pos, nbr, valid, cfg)
    F = jax.random.uniform(kf, (n,), jnp.float32, 100, 700)
    d_tx_e = jnp.where(adj_e, 1e4 / cap_e, 1e30)
    got = jax.jit(phi_update_op)(F * 1.5, F, Neighborhood(adj_e, nbr), d_tx_e)
    want = jax.jit(phi_update_sparse)(F * 1.5, F, adj_e, nbr, d_tx_e)
    log(f"[b] parity inputs: {int(jnp.sum(adj_e))} edges, max degree "
        f"{int(jnp.max(jnp.sum(adj_e, axis=1)))} of K={cfg.neighbor_k}")
    check_close(got, want, f"b:phi_update_op (lists) vs phi_update_sparse "
                f"N={n}", F32_RTOL)

    # sparse == dense, bit for bit, where K covers every degree
    small = dataclasses.replace(paper, num_workers=small_n, sim_time_s=5.0)
    small_sp = dataclasses.replace(small, neighbor_mode="sparse",
                                   neighbor_k=small_n - 1)
    dense, _ = timed_batch(key, small, DISTRIBUTED, small_n, small_runs)
    sparse, _ = timed_batch(key, small_sp, DISTRIBUTED, small_n, small_runs)
    for k in dense:
        np.testing.assert_array_equal(sparse[k], dense[k],
                                      err_msg=f"b:sparse vs dense {k}")
    log(f"[b] sparse == dense bit for bit at N={small_n}, K={small_n - 1}, "
        f"{small_runs} runs, all {len(dense)} metrics")


def phase_serve(seed: int, cfg, batch: int = 4, seq: int = 128,
                executors: int = 4, requests: int = 4) -> None:
    key = jax.random.PRNGKey(seed)
    kp, kt, kq = jax.random.split(key, 3)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(kp))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[c] {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {n_params} parameters in "
        f"{cfg.param_dtype}, initialized in {time.perf_counter() - t0} s")

    # attention kernels against their references at this model's widths
    G, hd = cfg.num_heads, cfg.head_dim_
    kv = cfg.num_kv_heads
    ka, kb, kc = jax.random.split(kq, 3)
    q = jax.random.normal(ka, (batch, 512, G, hd), jnp.bfloat16)
    k = jax.random.normal(kb, (batch, 512, kv, hd), jnp.bfloat16)
    v = jax.random.normal(kc, (batch, 512, kv, hd), jnp.bfloat16)
    check_close(jax.jit(ops.flash_attention)(q, k, v),
                jax.jit(ref.flash_attention)(q, k, v),
                "c:flash_attention vs ref", **BF16_TOL)
    kd = jnp.concatenate([k, k], axis=1)
    vd = jnp.concatenate([v, v], axis=1)
    pos = jnp.int32(700)
    check_close(jax.jit(ops.decode_attention)(q[:, 0], kd, vd, pos),
                jax.jit(ref.decode_attention)(q[:, 0], kd, vd, pos),
                "c:decode_attention vs ref", **BF16_TOL)

    # the unsplit forward pass: the reference, and the kernel's presence
    toks = jax.random.randint(kt, (requests, batch, seq), 0, cfg.vocab_size)
    fwd = jax.jit(lambda p, b: model.forward(p, b)[0])
    t0 = time.perf_counter()
    fwd_c = fwd.lower(params, {"tokens": toks[0]}).compile()
    t_compile = time.perf_counter() - t0
    if not has_kernel(fwd_c):
        raise AssertionError(f"c: seq {seq} does not reach flash attention")
    t0 = time.perf_counter()
    want = jax.block_until_ready(fwd_c(params, {"tokens": toks[0]}))
    log(f"[c] smoke timing, unsplit forward B={batch} S={seq}: compile "
        f"{t_compile} s, execute {time.perf_counter() - t0} s; it holds the "
        "flash-attention kernel")

    rng = np.random.default_rng(seed)
    F = np.maximum(rng.normal(400, 100, executors), 50.0)
    plan = plan_stages(cfg, F)
    log(f"[c] stage boundaries {plan.boundaries} on executors "
        f"{plan.executors}")
    eng = SplitServeEngine(cfg, params, plan)
    t0 = time.perf_counter()
    rid = eng.submit({"tokens": toks[0]})
    # 1 s epochs keep the queue's growth rate under the early-exit
    # thresholds, so this request runs every layer
    stats = eng.drain(dt=1.0)
    got = jax.block_until_ready(eng.results[rid])
    t_first = time.perf_counter() - t0
    layers = int(stats.records[-1][schema.LAYERS])
    if layers != cfg.num_layers:
        raise AssertionError(f"c: request 0 exited at layer {layers}")
    check_close(got, want, "c:split logits vs unsplit forward", **BF16_TOL)
    t0 = time.perf_counter()
    for r in range(1, requests):
        eng.submit({"tokens": toks[r]})
        eng.step()
    stats = eng.drain()
    for rid_r in range(1, requests):
        jax.block_until_ready(eng.results[rid_r])
    t_rest = time.perf_counter() - t0
    if stats.completed != requests * batch:
        raise AssertionError(f"c: {stats.completed} of {requests * batch} "
                             "rows completed")
    for rid_r in range(requests):
        lg = np.asarray(eng.results[rid_r], np.float32)
        if lg.shape != (batch, seq, cfg.vocab_size) or \
                not np.all(np.isfinite(lg)):
            raise AssertionError(f"c: request {rid_r} logits {lg.shape}")
    log(f"[c] smoke timing, SplitServeEngine: first request {t_first} s "
        f"(compiles {len(plan.executors)} stages + head), "
        f"{requests - 1} more requests {t_rest} s; exits "
        f"{stats.exit_counts}")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"[c] peak device memory {mem.get('peak_bytes_in_use')} bytes of "
        f"{mem.get('bytes_limit')}")


def phase_sharded(seed: int, cfg: SwarmConfig, chips: int) -> None:
    key = jax.random.PRNGKey(seed)
    n, runs = cfg.num_workers, cfg.num_runs
    one, spans_v = timed_batch(key, cfg, DISTRIBUTED, n, runs, "vmap")
    log(f"[sharded] smoke timing, vmap on one chip: compile "
        f"{spans_v['_compile_s']} s, execute {spans_v['_execute_s']} s")
    many, spans_s = timed_batch(key, cfg, DISTRIBUTED, n, runs, "sharded")
    log(f"[sharded] smoke timing, sharded over {chips} chips: compile "
        f"{spans_s['_compile_s']} s, execute {spans_s['_execute_s']} s")
    check_metrics(many, "sharded")
    differ = [k for k in one if not np.array_equal(one[k], many[k])]
    if differ:
        raise AssertionError(f"sharded != vmap in {differ}")
    log(f"[sharded] sharded over {chips} chips == vmap on one chip, bit for "
        f"bit, all {len(one)} metrics of {runs} runs")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    enable_compile_cache()
    log(f"devices: {len(devices)} x {devices[0].device_kind}; jax "
        f"{jax.__version__}")

    phases = ([("sharded", lambda: phase_sharded(args.seed, SwarmConfig(),
                                                 args.chips))]
              if args.chips > 1 else
              [("a", lambda: phase_paper(args.seed, SwarmConfig())),
               ("b", lambda: phase_sparse(args.seed)),
               ("c", lambda: phase_serve(args.seed,
                                         get_config("qwen3-1.7b")))])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        log(f"[{name}] phase passed in {time.perf_counter() - t0} s "
            "(smoke timing, compile included)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
