"""Vectorized time-stepped swarm simulator (paper §5 environment).

One simulation = ``lax.scan`` over decision epochs (Δt = 200 ms); each epoch
refreshes the scenario (mobility → positions, channel → adjacency/capacity,
fault → alive mask), runs the offloading strategy's decision rule once
(Alg. 1), then an inner scan over fine ticks (default 10 ms) advances
compute, transfers and Markov task arrivals.  The whole thing jits and
``vmap``s over Monte-Carlo runs (50 per the paper).

This module is only the scan skeleton + strategy dispatch; the parts live in
  * ``swarm/scenario.py`` — mobility/channel/fault registries + arrivals,
  * ``swarm/queues.py``   — struct-of-arrays task-queue ops,
  * ``swarm/transfer.py`` — transfer initiate/progress/deliver,
  * ``swarm/neighbors.py`` — the epoch's links in the configured format,
and Algorithm 1 itself (φ, the Distributed decision, the early exit) is
``core.protocol.decision_epoch``, whose φ update dispatches through
``kernels/ops`` (Pallas on TPU, jnp reference elsewhere).

Strategies (paper §5): 0 LocalOnly · 1 Random · 2 RandomAcyclic · 3 Greedy ·
4 Distributed (ours, diffusive φ).  The strategy id is a *traced* scalar so
all five share one executable; the scenario is *static* config, so sweeping
scenarios costs one compile per (cfg, n) pair and zero code edits.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import SwarmConfig
from repro.core.early_exit import CongestionState, exit_accuracy
from repro.core.neighborhood import Neighborhood
from repro.core.protocol import ProtocolState, decision_epoch
from repro.obs.scopes import phase
from repro.swarm import transfer as transfer_mod
from repro.swarm.neighbors import epoch_links
from repro.swarm.queues import (head_slot, head_visited, hop_count, push,
                                queued_gflops, slot_add, slot_mask,
                                slot_read, slot_write, visited_neighbors,
                                visited_words)
from repro.swarm.scenario import burst_arrivals, get_fault, get_mobility
from repro.swarm.tasks import (PROFILE_KEY, ProfileMix, draw_profiles,
                               is_mix, make_profile, pick)
from repro.trace import record as trace_record

BIG = 1e30

LOCAL_ONLY, RANDOM, RANDOM_ACYCLIC, GREEDY, DISTRIBUTED = range(5)
STRATEGY_NAMES = ("LocalOnly", "Random", "RandomAcyclic", "Greedy",
                  "Distributed")


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


def init_state(key, cfg: SwarmConfig, n: int) -> Dict:
    Q = cfg.queue_slots
    # one split, three independent subkeys (R001): the init key used to be
    # dual-derived — split(key) for capability/mobility AND fold_in(key, 7)
    # for faults, two sink families off one threefry counter.  The default-
    # scenario streams this moves are pinned by test_default_scenario_rng_pin.
    kf, km, k_fault = jax.random.split(key, 3)
    F = jnp.maximum(
        cfg.capability_mean
        + cfg.capability_std * jax.random.normal(kf, (n,), jnp.float32),
        50.0)
    return {
        "mob": get_mobility(cfg).init(km, cfg, n),
        "alive": get_fault(cfg).init(k_fault, cfg, n),
        "F": F,
        # queues (struct-of-arrays)
        "q_active": jnp.zeros((n, Q), bool),
        "q_cum": jnp.zeros((n, Q), jnp.float32),
        "q_created": jnp.zeros((n, Q), jnp.float32),
        "q_seq": jnp.zeros((n, Q), jnp.int32),
        # packed visited sets: bit j % 32 of word j // 32 (queues.py)
        "q_visited": jnp.zeros((visited_words(n), n, Q), jnp.uint32),
        "seq_counter": jnp.int32(0),
        # single outgoing transfer per node (§3.2)
        "tx_active": jnp.zeros((n,), bool),
        "tx_dst": jnp.zeros((n,), jnp.int32),
        "tx_bits": jnp.zeros((n,), jnp.float32),
        "tx_cum": jnp.zeros((n,), jnp.float32),
        "tx_created": jnp.zeros((n,), jnp.float32),
        "tx_visited": jnp.zeros((visited_words(n), n), jnp.uint32),
        "tx_start": jnp.zeros((n,), jnp.float32),
        # protocol state
        "phi": F,
        "cong_prev": jnp.zeros((n,), jnp.float32),
        "cong_D": jnp.zeros((n,), jnp.float32),
        "xi_layers": jnp.full((n,), cfg.exit_points[2], jnp.int32),
        "xi_label": jnp.zeros((n,), jnp.int32),
        # Markov-modulated arrival chain (bursty workload, Fig. 1)
        "burst_on": jnp.zeros((n,), bool),
        # metric accumulators; event *counts* carry as i32 — integer
        # accumulation is exact under any reduction order, so the in-scan
        # cross-node count sums stay bit-identical across the executor
        # backends' different batchings (swarmlint J001, DESIGN.md §8.2)
        "done_count": jnp.int32(0), "lat_sum": jnp.float32(0),
        "acc_sum": jnp.float32(0), "proc_gflops": jnp.zeros((n,), jnp.float32),
        # energy accrues per node, not as a swarm scalar: elementwise
        # accumulation is bit-identical under any batching (vmap, sharded,
        # streaming chunks), whereas an in-scan cross-node sum reassociates
        # with the batch shape and breaks backend parity at the ulp level
        "e_comp": jnp.zeros((n,), jnp.float32),
        "e_tx": jnp.zeros((n,), jnp.float32),
        "tx_count": jnp.int32(0), "tx_delivered": jnp.int32(0),
        "tx_time_sum": jnp.float32(0),
        "drop_count": jnp.int32(0), "gen_count": jnp.int32(0),
        # per-task + per-hop telemetry (repro.trace): {} when the
        # capacities are 0, so the untraced state pytree — and every
        # number downstream — is exactly the historical one
        **trace_record.init_trace(cfg, n),
        **trace_record.init_hops(cfg, n),
        **trace_record.init_state_stream(cfg, n),
        # per-task profile ids and completions per profile: only under a
        # task mix, so a one-profile state is exactly the historical one
        **({"q_profile": jnp.zeros((n, Q), jnp.int32),
            "tx_profile": jnp.zeros((n,), jnp.int32),
            "done_by_profile": jnp.zeros((len(cfg.task_profiles),),
                                         jnp.int32)}
           if is_mix(cfg) else {}),
    }


# ---------------------------------------------------------------------------
# per-tick dynamics
# ---------------------------------------------------------------------------


def _compute_pass(st, budget, targets_cum, t_now, cfg: SwarmConfig,
                  mix: ProfileMix | None = None):
    """Advance each node's head task by up to `budget` GFLOPs: to
    ``targets_cum``, or under a task mix to the full depth of the head
    task's own profile."""
    eJ = cfg.energy_per_gflop_j
    n, Q = st["q_active"].shape
    rows = jnp.arange(n)
    head, has = head_slot(st)
    at_head = slot_mask(head, Q)
    if mix is not None:
        with phase("task_profile"):
            pid_h = slot_read(st["q_profile"], at_head)
            targets_cum = pick(mix.done_gflops, pid_h)
    cur = slot_read(st["q_cum"], at_head)
    rem = jnp.maximum(targets_cum - cur, 0.0)
    adv = jnp.where(has, jnp.minimum(budget, rem), 0.0)
    new_cum = cur + adv
    completed = has & (new_cum >= targets_cum - 1e-6)
    lat = t_now - slot_read(st["q_created"], at_head)
    acc = exit_accuracy(st["xi_label"], cfg.exit_accuracy)

    st = dict(st)
    st["q_cum"] = slot_write(st["q_cum"], at_head & has[:, None], new_cum)
    st["proc_gflops"] = st["proc_gflops"] + adv
    st["e_comp"] = st["e_comp"] + adv * eJ
    # dtype-pinned i32 count (bool sums widen to i64 under x64 — J002)
    st["done_count"] = st["done_count"] + jnp.sum(completed,
                                                  dtype=jnp.int32)
    st["lat_sum"] = st["lat_sum"] + jnp.sum(jnp.where(completed, lat, 0.0))
    st["acc_sum"] = st["acc_sum"] + jnp.sum(jnp.where(completed, acc, 0.0))
    if mix is not None:
        with phase("task_profile"):
            st["done_by_profile"] = st["done_by_profile"] + jnp.sum(
                (pid_h[:, None] == jnp.arange(len(mix.names)))
                & completed[:, None], axis=0, dtype=jnp.int32)
    with phase("queues"):   # a completed head leaves its slot: a pop
        st["q_active"] = st["q_active"] & ~(at_head & completed[:, None])
    if trace_record.enabled(cfg):
        with phase("trace_capture"):
            # adding at an empty queue's slot 0 is harmless: adv == 0 there
            st["q_energy"] = slot_add(st["q_energy"], at_head, adv * eJ)
            with phase("visited"):
                hops = hop_count(head_visited(st["q_visited"], at_head))
            st = trace_record.write_records(
                st, completed, seq=slot_read(st["q_seq"], at_head),
                src=slot_read(st["q_src"], at_head), dst=rows,
                created_t=slot_read(st["q_created"], at_head),
                completed_t=t_now, exit_label=st["xi_label"],
                layers=st["xi_layers"], hops=hops,
                energy_j=slot_read(st["q_energy"], at_head),
                tx_time_s=slot_read(st["q_txtime"], at_head))
    return st, budget - adv


def _tick(st, key, cfg: SwarmConfig, profile, rate, alive, t_now):
    n = st["F"].shape[0]
    tick = cfg.tick_s
    mix = profile if isinstance(profile, ProfileMix) else None

    # (a) Markov-modulated arrivals (down nodes don't generate); under a
    #     task mix each arrival draws its profile off a key folded from the
    #     tick key, which leaves the arrival draws as they were
    st = dict(st)
    with phase("arrivals"):
        st["burst_on"], arrive = burst_arrivals(st["burst_on"], key, cfg)
        arrive = arrive & alive
        pid = None
        if mix is not None:
            with phase("task_profile"):
                pid = draw_profiles(jax.random.fold_in(key, PROFILE_KEY),
                                    mix, n)
        fresh = jnp.zeros_like(st["tx_visited"])     # no node visited yet
        if trace_record.enabled(cfg):
            st = trace_record.traced_push(
                st, arrive, jnp.zeros((n,), jnp.float32),
                jnp.full((n,), t_now), fresh,
                src=jnp.arange(n), energy=0.0,
                txtime=0.0, t_now=t_now, cfg=cfg, profile=pid)
        else:
            st = push(st, arrive, jnp.zeros((n,), jnp.float32),
                      jnp.full((n,), t_now), fresh,
                      None if pid is None else {"profile": pid})
        st["gen_count"] = st["gen_count"] + jnp.sum(arrive, dtype=jnp.int32)

    # (b) compute (budget cascade x2: finish a task and start the next;
    #     down nodes hold their queues but burn no cycles)
    with phase("compute"):
        if mix is None:
            targets = profile.cum_gflops[jnp.clip(st["xi_layers"], 0,
                                                  profile.gflops.shape[0])]
        else:
            targets = None          # each head task's own full depth
        budget = jnp.where(alive, st["F"] * tick, 0.0)
        for _ in range(2):
            st, budget = _compute_pass(st, budget, targets, t_now, cfg, mix)

    # (c) transfer progress + delivery
    with phase("transfers"):
        return transfer_mod.progress(st, rate, alive, cfg, t_now)


# ---------------------------------------------------------------------------
# epoch decision (strategy dispatch)
# ---------------------------------------------------------------------------


def _strategy_decision(st, strategy, nbhd: Neighborhood, d_tx, T, key,
                       cfg: SwarmConfig):
    """Returns (do_transfer [N] bool, target [N] i32, EpochDecision).

    Every arm reduces over the neighbourhood's slot axis and maps the
    chosen column back to a node id, so one body serves both formats.
    Offload coins and Greedy/Distributed targets are the same in either
    (id-sorted lists keep the lowest-index tie-break); Random and
    RandomAcyclic draw their target gumbels over ``nbhd.mask``'s shape,
    per node pair dense and per slot for lists — different streams, each
    uniform over the same neighbour sets.
    """
    n = st["F"].shape[0]
    k1, k2, k3 = jax.random.split(key, 3)
    head, has = head_slot(st)
    has_nbr = jnp.any(nbhd.mask, axis=1)

    # ---- Distributed (ours): Alg. 1 lines 2-11, kernel-dispatched --------
    ep = decision_epoch(
        ProtocolState(st["phi"], CongestionState(st["cong_prev"],
                                                 st["cong_D"])),
        F=st["F"], nbhd=nbhd, d_tx=d_tx, queued_gflops=T, gamma=cfg.gamma,
        dt=cfg.decision_period_s, alpha=cfg.ema_alpha,
        tau_med=cfg.exit_thresholds[0], tau_high=cfg.exit_thresholds[1],
        exit_points=cfg.exit_points,
        finalize_layers=cfg.exit_finalize_layers,
        early_exit_enabled=cfg.early_exit_enabled)
    dist = (ep.decision.transfer, ep.decision.target)

    # ---- Greedy: least instantaneous load, w.p. p_greedy -----------------
    cand = jnp.where(nbhd.mask, nbhd.view(T), BIG)
    g_tgt = nbhd.node(jnp.argmin(cand, axis=1))
    g_less = jnp.min(cand, axis=1) < T
    g_do = (jax.random.bernoulli(k1, cfg.greedy_offload_p, (n,))
            & has_nbr & g_less)
    greedy = (g_do, g_tgt)

    # ---- Random: uniform neighbor, w.p. 0.2 ------------------------------
    # NB: the offload coin must not share k2 with the gumbel target draw —
    # threefry counters would make coin u_j bit-identical to a target score
    # for j, correlating "who offloads" with "who gets picked"
    gum = jax.random.gumbel(k2, nbhd.mask.shape)
    r_tgt = nbhd.node(jnp.argmax(jnp.where(nbhd.mask, gum, -BIG), axis=1))
    r_do = jax.random.bernoulli(jax.random.fold_in(k2, 1),
                                cfg.random_offload_p, (n,)) & has_nbr
    random_ = (r_do, r_tgt)

    # ---- RandomAcyclic: uniform unvisited neighbor, w.p. 0.1 -------------
    with phase("visited"):
        visited = visited_neighbors(head_visited(
            st["q_visited"], slot_mask(head, st["q_active"].shape[1])), nbhd)
    amask = nbhd.mask & ~visited
    a_has = jnp.any(amask, axis=1)
    a_tgt = nbhd.node(jnp.argmax(
        jnp.where(amask, jax.random.gumbel(k3, nbhd.mask.shape), -BIG),
        axis=1))
    a_do = jax.random.bernoulli(jax.random.fold_in(k3, 1),
                                cfg.random_acyclic_p, (n,)) & a_has
    acyc = (a_do, a_tgt)

    local = (jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32))

    do = jax.lax.switch(strategy, [
        lambda: local[0], lambda: random_[0], lambda: acyc[0],
        lambda: greedy[0], lambda: dist[0]])
    tgt = jax.lax.switch(strategy, [
        lambda: local[1], lambda: random_[1], lambda: acyc[1],
        lambda: greedy[1], lambda: dist[1]])
    return do, tgt, ep


def _transfer_bits_per_gflop(st, profile):
    """Bits per GFLOP of the transfer-delay estimate d_tx: the profile's,
    or under a task mix a column of the head task's profile per node (the
    share-weighted mean where the queue is empty)."""
    if not isinstance(profile, ProfileMix):
        return profile.bits_per_gflop
    with phase("task_profile"):
        head, has = head_slot(st)
        pid_h = slot_read(st["q_profile"],
                          slot_mask(head, st["q_active"].shape[1]))
        bpg = jnp.where(has, pick(profile.bits_per_gflop, pid_h),
                        jnp.float32(profile.idle_bits_per_gflop))
        return bpg[:, None]


def _epoch(st, key, epoch_idx, strategy, cfg: SwarmConfig, profile):
    t0 = epoch_idx.astype(jnp.float32) * cfg.decision_period_s
    # kd/kt reproduce the pre-engine key streams exactly; scenario keys are
    # folded off the epoch key so the default scenario stays bit-identical
    # (except Random/RandomAcyclic, whose key-reuse fix below is deliberate).
    with phase("keys"):
        kd, kt = jax.random.split(key)
        k_mob = jax.random.fold_in(key, 11)
        k_ch = jax.random.fold_in(key, 13)
        k_fault = jax.random.fold_in(key, 17)

    # 1. refresh the scenario at epoch start; 2. the epoch's links in the
    #    configured representation (swarm/neighbors.py); 3. strategy
    #    decision and early exit (Alg. 1 lines 2-11, Eqs. 10-16)
    st = dict(st)
    with phase("faults"):
        st["alive"] = get_fault(cfg).step(st["alive"], k_fault, cfg)
    with phase("mobility"):
        st["mob"], pos = get_mobility(cfg).step(st["mob"], k_mob, cfg, t0)
    with phase("decision"):
        T = queued_gflops(st, profile)
        bpg = _transfer_bits_per_gflop(st, profile)
    nbhd, cap, rate_to = epoch_links(pos, st["alive"], cfg, k_ch)
    with phase("channel"):
        d_tx = jnp.where(nbhd.mask, bpg / cap, BIG)
    with phase("decision"):
        do, tgt, ep = _strategy_decision(st, strategy, nbhd, d_tx, T, kd,
                                         cfg)
    st["phi"] = ep.state.phi
    st["cong_prev"], st["cong_D"] = ep.state.congestion
    st["xi_label"], st["xi_layers"] = ep.exit_lbl, ep.exit_layers

    # 4. initiate transfers: pop head, snap to boundary (§3.1 discard)
    with phase("initiate"):
        _, has = head_slot(st)
        elig = do & has & ~st["tx_active"] & (tgt >= 0)
        st = transfer_mod.initiate(st, elig, tgt, t0, profile)

    # 5. fine ticks.  tx_dst is frozen between decisions, so each node's
    #    outgoing link rate [N] is resolved once per epoch
    with phase("channel"):
        rate = rate_to(st["tx_dst"])
    n_ticks = int(round(cfg.decision_period_s / cfg.tick_s))

    def tick_body(st, i):
        t_now = t0 + (i.astype(jnp.float32) + 1.0) * cfg.tick_s
        with phase("keys"):
            k = jax.random.fold_in(kt, i)
        st = _tick(st, k, cfg, profile, rate, st["alive"], t_now)
        return st, None

    st, _ = jax.lax.scan(tick_body, st, jnp.arange(n_ticks))

    # 6. flight recorder: snapshot node gauges + system aggregates at the
    #    end of every trace_state_every-th epoch (DESIGN.md §12)
    if trace_record.state_enabled(cfg):
        with phase("trace_capture"):
            st = trace_record.write_state(st, epoch_idx,
                                          t0 + cfg.decision_period_s, cfg)
    return st


# ---------------------------------------------------------------------------
# run + metrics
# ---------------------------------------------------------------------------


def run_sim(key, cfg: SwarmConfig, strategy, n: int | None = None) -> Dict:
    """One full simulation; returns the metric dict (see summarize)."""
    n = n or cfg.num_workers
    profile = make_profile(cfg)
    with phase("init"):
        k_init, k_run = jax.random.split(key)
        st = init_state(k_init, cfg, n)
    n_epochs = int(round(cfg.sim_time_s / cfg.decision_period_s))

    def body(st, i):
        with phase("keys"):
            k = jax.random.fold_in(k_run, i)
        st = _epoch(st, k, i, strategy, cfg, profile)
        return st, None

    st, _ = jax.lax.scan(body, st, jnp.arange(n_epochs))
    with phase("summarize"):
        return summarize(st, cfg, profile)


def summarize(st, cfg: SwarmConfig, profile) -> Dict:
    # the i32 event counters re-enter float land here, outside the scan:
    # counts are exact in f32 up to 2^24, so every reported metric is
    # bit-identical to the historical f32-accumulator values
    done_f = st["done_count"].astype(jnp.float32)
    done = jnp.maximum(done_f, 1.0)
    rem_q = queued_gflops(st, profile)
    if isinstance(profile, ProfileMix):
        with phase("task_profile"):
            tx_total = pick(profile.total_gflops, st["tx_profile"])
    else:
        tx_total = profile.total_gflops
    rem_tx = jnp.where(st["tx_active"], tx_total - st["tx_cum"], 0.0)
    # Jain fairness over capability-normalized processed GFLOPs (Fig. 4d)
    x = st["proc_gflops"] / st["F"]
    jain = (jnp.sum(x) ** 2) / (x.shape[0] * jnp.sum(x * x) + 1e-12)
    tps = done_f / cfg.sim_time_s
    acc = st["acc_sum"] / done
    # single cross-node reduction, outside the scan (see init_state note)
    e_total = jnp.sum(st["e_comp"] + st["e_tx"])
    ae = e_total / done
    al = st["lat_sum"] / done
    fom = tps * acc / jnp.maximum(ae * al, 1e-12)
    out = {
        "completed": done_f,
        "generated": st["gen_count"].astype(jnp.float32),
        "avg_latency_s": al, "avg_accuracy": acc,
        "remaining_gflops": jnp.sum(rem_q) + jnp.sum(rem_tx),
        # mean over *delivered* transfers: tx_time_sum only accumulates at
        # delivery, so dividing by initiations (tx_count) would bias the
        # mean low whenever transfers are still in flight at sim end
        "avg_transfer_time_s": st["tx_time_sum"]
        / jnp.maximum(st["tx_delivered"].astype(jnp.float32), 1.0),
        "transfers": st["tx_count"].astype(jnp.float32),
        "transfers_delivered": st["tx_delivered"].astype(jnp.float32),
        "jain_fairness": jain,
        "energy_per_task_j": ae,
        "energy_total_j": e_total,
        "throughput_tps": tps,
        "dropped": st["drop_count"].astype(jnp.float32),
        "fom": fom,
    }
    if isinstance(profile, ProfileMix):
        for p, name in enumerate(profile.names):
            out[f"completed_{name}"] = \
                st["done_by_profile"][p].astype(jnp.float32)
    if trace_record.enabled(cfg):
        # per-task telemetry rides next to the scalar metrics; downstream
        # consumers key off the trace_ prefix (report skips ci95 for them,
        # decode/aggregate turn them into task-level indices)
        out["trace_records"] = st["trace_records"]
        out["trace_overflow"] = st["trace_overflow"]
    if trace_record.hops_enabled(cfg):
        # the per-hop stream, same conventions (trace_ prefix, decoded
        # into hop-resolved indices by trace.decode_hops/hop_indices)
        out["trace_hops"] = st["trace_hops"]
        out["trace_hop_overflow"] = st["trace_hop_overflow"]
    if trace_record.state_enabled(cfg):
        # the epoch-indexed flight recorder (decode_state/state_indices)
        out["trace_state"] = st["trace_state"]
        out["trace_state_sys"] = st["trace_state_sys"]
        out["trace_state_epochs"] = st["trace_state_epochs"]
    return out


def run_many(key, cfg: SwarmConfig, strategy, n: int, num_runs: int) -> Dict:
    """vmap over Monte-Carlo runs; returns dict of [num_runs] arrays.

    Routed through ``repro.fleet.executor`` (the ``vmap`` backend is the
    historical jitted-vmap path, bit-identical), so the simulator and the
    fleet sweep engine share one batching implementation.  For multi-device
    or memory-bounded batching call ``fleet.run_batch`` with
    ``backend="sharded"`` / ``"streaming"`` instead.
    """
    from repro.fleet.executor import run_batch  # deferred: no import cycle
    return run_batch(key, cfg, strategy, n, num_runs, backend="vmap")
