"""Plain reference of the swarm simulator, written from the paper's model
(arXiv:2503.16146 §3, §5, Eqs. 3-4, 9-16) and the configuration file alone.

It imports nothing of the program under test.  It runs the same model on
the same data: every random draw is taken from the run key by the same
derivation (``split`` / ``fold_in`` on the epoch and tick indices), so a
correct program and this reference agree run by run, statistic by
statistic, up to float rounding.  It is written for reading, not speed:

* one run is a ``lax.scan`` over 200 ms decision epochs, each with an inner
  scan over 10 ms ticks;
* it carries only the state that the strategy it runs reads: the
  per-task visited sets [N, Q, N] only for RandomAcyclic, no telemetry
  streams;
* the sparse neighbor lists are computed from the full [N, N] distance
  matrix, not from a spatial hash.

Supported: circular mobility, two-ray channel, no faults, the Distributed
(4), RandomAcyclic (2) and LocalOnly (0) strategies, dense or sparse
neighbor lists, early exit on or off.  Anything else raises, so a new configuration cannot be
compared against the wrong model in silence.

``dtype`` is the float type of every state array and every computation.
float32 is the configuration's own; bfloat16 is the control, the next
precision below (random draws are made in float32 and then rounded, so
the control differs by arithmetic precision alone).
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

STATS = ("completed", "generated", "avg_latency_s", "avg_accuracy",
         "remaining_gflops", "avg_transfer_time_s", "transfers",
         "transfers_delivered", "jain_fairness", "energy_per_task_j",
         "energy_total_j", "throughput_tps", "dropped", "fom")
STRATEGIES = {"LocalOnly": 0, "RandomAcyclic": 2, "Distributed": 4}
BIG = 1e30
NEG = -1e30
INT_MAX = np.iinfo(np.int32).max


def _check_supported(cfg: Dict, strategy: str) -> None:
    want = {"mobility_model": "circular", "channel_model": "two_ray",
            "fault_model": "none", "trace_capacity": 0,
            "trace_hop_capacity": 0, "trace_state_every": 0}
    bad = {k: cfg[k] for k, v in want.items() if cfg[k] != v}
    if bad or strategy not in STRATEGIES or \
            cfg["neighbor_mode"] not in ("dense", "sparse"):
        raise NotImplementedError(
            f"the reference models {want}, strategies {sorted(STRATEGIES)} "
            f"and dense/sparse neighbors; got {bad}, {strategy!r}, "
            f"{cfg['neighbor_mode']!r}")


def task_profile(cfg: Dict):
    """Per-layer GFLOPs decay linearly 2 -> 0.5; activations start at a
    0.5 MB input and decay geometrically 2 MB -> 64 KB."""
    L = cfg["task_layers"]
    w = np.linspace(2.0, 0.5, L)
    g = w / w.sum() * cfg["task_gflops_total"]
    cum = np.concatenate([[0.0], np.cumsum(g)])
    act_bits = np.concatenate([[0.5e6], np.geomspace(2.0e6, 64e3, L)]) * 8.0
    bits_per_gflop = float(act_bits[1:].mean()) / float(g.mean())
    return cum, act_bits, bits_per_gflop


def comm_range(cfg: Dict) -> float:
    """Two-ray distance at which SNR falls to snr_min_db (or the
    configured candidate radius)."""
    if cfg["neighbor_range_m"] > 0.0:
        return cfg["neighbor_range_m"]
    budget = cfg["tx_power_dbm"] - cfg["noise_dbm"] - cfg["snr_min_db"]
    h2 = cfg["altitude_m"] * cfg["altitude_m"]
    r = 10.0 ** ((budget + 20.0 * math.log10(h2)) / 40.0)
    return min(r, cfg["area_m"] * math.sqrt(2.0))


def grid(cfg: Dict, n: int, k: int):
    """The candidate grid that defines the sparse neighbor lists: cell
    edge, cell count per side and how many nodes of a cell are candidates
    (DESIGN.md §11)."""
    r = comm_range(cfg)
    area = cfg["area_m"]
    target = max(min(r, 0.75 * area * math.sqrt(max(k, 1) / max(n, 1))),
                 area / 256)
    G = max(int(area / target), 1)
    if cfg["neighbor_cell_cap"] > 0:
        cap = cfg["neighbor_cell_cap"]
    elif n <= 1024:
        cap = n
    else:
        lam = n / float(G * G)
        cap = max(2 * k, int(math.ceil(4.0 * lam)) + 8)
    return G, area / G, min(cap, n), r


def simulate(key, cfg: Dict, strategy: str, dtype=jnp.float32):
    """One run; returns the 14 statistics as scalars of ``dtype``."""
    _check_supported(cfg, strategy)
    ft = dtype
    n = cfg["num_workers"]
    Q = cfg["queue_slots"]
    L = cfg["task_layers"]
    L1, L2, Lf = cfg["exit_points"]
    fin = cfg["exit_finalize_layers"]
    sparse = cfg["neighbor_mode"] == "sparse"
    acyclic = strategy == "RandomAcyclic"
    cum_np, act_np, bits_per_gflop = task_profile(cfg)
    cum = jnp.asarray(cum_np, jnp.float32).astype(ft)
    act_bits = jnp.asarray(act_np, jnp.float32).astype(ft)
    total = float(cfg["task_gflops_total"])
    tick = cfg["tick_s"]
    dp = cfg["decision_period_s"]
    rows = jnp.arange(n, dtype=jnp.int32)

    def f(x):
        return jnp.asarray(x, jnp.float32).astype(ft)

    # --- initial state ----------------------------------------------------
    k_init, k_run = jax.random.split(key)
    kf, km, _ = jax.random.split(k_init, 3)
    F = jnp.maximum(f(cfg["capability_mean"] + cfg["capability_std"]
                      * jax.random.normal(kf, (n,), jnp.float32)), 50.0)
    kc, kp, kj = jax.random.split(km, 3)
    g = cfg["placement_granularity"]
    cell_idx = jax.random.randint(kc, (n, 2), 0, g)
    jitter = jax.random.uniform(kj, (n, 2), jnp.float32, 0.25, 0.75)
    center = (f(cell_idx.astype(jnp.float32)) + f(jitter)) \
        * (cfg["area_m"] / g)
    phase0 = f(jax.random.uniform(kp, (n,), jnp.float32, 0.0, 2.0 * np.pi))
    omega = jnp.full((n,), cfg["speed_mps"] / cfg["movement_radius_m"], ft)

    st = dict(
        q_active=jnp.zeros((n, Q), bool), q_cum=jnp.zeros((n, Q), ft),
        q_created=jnp.zeros((n, Q), ft), q_seq=jnp.zeros((n, Q), jnp.int32),
        seq_counter=jnp.int32(0),
        tx_active=jnp.zeros((n,), bool), tx_dst=jnp.zeros((n,), jnp.int32),
        tx_bits=jnp.zeros((n,), ft), tx_cum=jnp.zeros((n,), ft),
        tx_created=jnp.zeros((n,), ft), tx_start=jnp.zeros((n,), ft),
        phi=F, cong_prev=jnp.zeros((n,), ft), cong_D=jnp.zeros((n,), ft),
        burst_on=jnp.zeros((n,), bool),
        done=jnp.int32(0), lat_sum=jnp.zeros((), ft),
        acc_sum=jnp.zeros((), ft), proc=jnp.zeros((n,), ft),
        e_comp=jnp.zeros((n,), ft), e_tx=jnp.zeros((n,), ft),
        tx_count=jnp.int32(0), tx_delivered=jnp.int32(0),
        tx_time_sum=jnp.zeros((), ft), dropped=jnp.int32(0),
        generated=jnp.int32(0))
    if acyclic:
        # the nodes a task has left, per queued task and per transfer
        st.update(q_visited=jnp.zeros((n, Q, n), bool),
                  tx_visited=jnp.zeros((n, n), bool))

    # --- helpers ----------------------------------------------------------
    def head(st):
        """FIFO head: the active slot with the lowest sequence number."""
        seqv = jnp.where(st["q_active"], st["q_seq"], INT_MAX)
        return jnp.argmin(seqv, axis=1), jnp.any(st["q_active"], axis=1)

    def load(st):
        """T_i: GFLOPs still to run over every queued task."""
        rem = jnp.maximum(total - st["q_cum"], 0.0)
        return jnp.sum(jnp.where(st["q_active"], rem, 0.0), axis=1)

    def enqueue(st, mask, cum_v, created_v, visited_v=None):
        """One task into the first free slot of every masked node; a node
        with no free slot drops it."""
        free = jnp.argmin(st["q_active"], axis=1)
        has_free = ~jnp.all(st["q_active"], axis=1)
        ok = mask & has_free
        seq = st["seq_counter"] + jnp.cumsum(ok.astype(jnp.int32),
                                             dtype=jnp.int32) - 1
        st = dict(st)
        for name, v in (("q_active", True), ("q_cum", cum_v),
                        ("q_created", created_v), ("q_seq", seq)):
            old = st[name][rows, free]
            st[name] = st[name].at[rows, free].set(jnp.where(ok, v, old))
        if acyclic:
            old = st["q_visited"][rows, free]
            st["q_visited"] = st["q_visited"].at[rows, free].set(
                jnp.where(ok[:, None], visited_v, old))
        st["seq_counter"] = st["seq_counter"] + jnp.sum(ok, dtype=jnp.int32)
        st["dropped"] = st["dropped"] + jnp.sum(mask & ~has_free,
                                                dtype=jnp.int32)
        return st

    def boundary_layer(cum_done):
        """Last whole layer reached: layer work is lost on offload."""
        lyr = jnp.sum(cum[None, :] <= cum_done[:, None], axis=1) - 1
        return jnp.clip(lyr, 0, L)

    def capacity(snr):
        return cfg["bandwidth_hz"] * jnp.log2(1.0 + jnp.power(10.0,
                                                              snr / 10.0))

    def snr_at(dist):
        pl = 40.0 * jnp.log10(jnp.maximum(dist, 1.0)) - 20.0 * jnp.log10(
            f(cfg["altitude_m"] * cfg["altitude_m"]))
        return cfg["tx_power_dbm"] - pl - cfg["noise_dbm"]

    if sparse:
        K = max(1, min(cfg["neighbor_k"], n - 1)) if n > 1 else 1
        G, cell_m, cell_cap, r = grid(cfg, n, K)

    def neighbors(pos):
        """The K nearest nodes within range among the candidates of the
        3 x 3 grid cells around each node (each cell offering its
        ``cell_cap`` lowest-id nodes), listed by ascending id."""
        ix = jnp.clip((pos[:, 0] / cell_m).astype(jnp.int32), 0, G - 1)
        iy = jnp.clip((pos[:, 1] / cell_m).astype(jnp.int32), 0, G - 1)
        cid = ix * G + iy
        same = cid[None, :] == cid[:, None]
        rank = jnp.sum(same & (rows[:, None] < rows[None, :]), axis=0)
        near = (jnp.abs(ix[:, None] - ix[None, :]) <= 1) & \
            (jnp.abs(iy[:, None] - iy[None, :]) <= 1)
        d2 = jnp.sum(jnp.square(pos[:, None, :] - pos[None, :, :]), axis=-1)
        ok = near & (rank < cell_cap)[None, :] & \
            (rows[:, None] != rows[None, :]) & (d2 <= f(r * r))
        score, sel = jax.lax.top_k(-jnp.where(ok, d2, jnp.inf), K)
        valid = score > -jnp.inf
        order = jnp.argsort(jnp.where(valid, sel, n), axis=1)
        nbr = jnp.take_along_axis(sel, order, axis=1).astype(jnp.int32)
        valid = jnp.take_along_axis(valid, order, axis=1)
        return jnp.where(valid, nbr, 0), valid

    # --- one epoch --------------------------------------------------------
    def epoch(st, i):
        key_e = jax.random.fold_in(k_run, i)
        t0 = f(i.astype(jnp.float32)) * dp
        kd, kt = jax.random.split(key_e)
        ang = phase0 + omega * t0
        pos = center + cfg["movement_radius_m"] * jnp.stack(
            [jnp.cos(ang), jnp.sin(ang)], axis=-1)
        T = load(st)

        # links (Eqs. 3, 4, 9) and the diffusive metric (Eq. 10)
        inv_phi = 1.0 / st["phi"]
        if sparse:
            nbr, valid = neighbors(pos)
            dist = jnp.sqrt(jnp.sum(jnp.square(pos[:, None, :] - pos[nbr]),
                                    axis=-1) + 1e-9)
            snr = snr_at(dist)
            adj = valid & (snr >= cfg["snr_min_db"])
            cap = jnp.where(adj, capacity(snr), 1.0)
            ids = nbr
        else:
            diff = pos[:, None, :] - pos[None, :, :]
            dist = jnp.sqrt(jnp.sum(jnp.square(diff), axis=-1) + 1e-9)
            snr = snr_at(dist)
            adj = (snr >= cfg["snr_min_db"]) & ~jnp.eye(n, dtype=bool)
            cap = jnp.where(adj, capacity(snr), 1.0)
            ids = jnp.broadcast_to(rows[None, :], (n, n))
        d_tx = jnp.where(adj, bits_per_gflop / cap, BIG)
        worst = jnp.max(jnp.where(adj, d_tx + inv_phi[ids], NEG), axis=1)
        deg = jnp.sum(adj, axis=1)
        inv_new = (1.0 / F + worst) / (deg.astype(ft) + 1.0)
        phi = jnp.where(deg > 0, 1.0 / inv_new, F)

        # decision (Eqs. 11-13): offload to the least utilized neighbor
        U = T / jnp.maximum(phi, 1e-9)
        cand = jnp.where(adj, U[ids], BIG)
        slot = jnp.argmin(cand, axis=1)
        has_nbr = deg > 0
        target = jnp.where(has_nbr, ids[rows, slot], -1)
        do = has_nbr & ((U - jnp.min(cand, axis=1)) > cfg["gamma"])
        if strategy == "LocalOnly":
            do = jnp.zeros((n,), bool)
        elif acyclic:
            # a uniformly drawn neighbor that the head task has not left,
            # offloaded to with probability random_acyclic_p
            k3 = jax.random.split(kd, 3)[2]
            h, _ = head(st)
            fresh = adj & ~st["q_visited"][rows, h][rows[:, None], ids]
            score = jnp.where(fresh, jax.random.gumbel(k3, adj.shape), NEG)
            target = ids[rows, jnp.argmax(score, axis=1)]
            do = jax.random.bernoulli(jax.random.fold_in(k3, 1),
                                      cfg["random_acyclic_p"], (n,)) & \
                jnp.any(fresh, axis=1)

        # congestion and early exit (Eqs. 14-16)
        D = st["cong_D"] + cfg["ema_alpha"] * (
            (T - st["cong_prev"]) / dp - st["cong_D"])
        if cfg["early_exit_enabled"]:
            tm, th = cfg["exit_thresholds"]
            label = jnp.where(D > th, 2, jnp.where(D > tm, 1, 0))
        else:
            label = jnp.zeros((n,), jnp.int32)
        layers = jnp.where(label == 2, min(L1 + fin, Lf),
                           jnp.where(label == 1, min(L2 + fin, Lf), Lf))
        acc_now = jnp.where(label == 2, f(cfg["exit_accuracy"][0]),
                            jnp.where(label == 1, f(cfg["exit_accuracy"][1]),
                                      f(cfg["exit_accuracy"][2])))
        st = dict(st, phi=phi, cong_prev=T, cong_D=D)

        # start transfers: the head task leaves at its last layer boundary
        h, has = head(st)
        go = do & has & ~st["tx_active"] & (target >= 0)
        cum_h = st["q_cum"][rows, h]
        lyr = boundary_layer(cum_h)
        st["tx_dst"] = jnp.where(go, target, st["tx_dst"])
        st["tx_bits"] = jnp.where(go, act_bits[lyr], st["tx_bits"])
        st["tx_cum"] = jnp.where(go, cum[lyr], st["tx_cum"])
        st["tx_created"] = jnp.where(go, st["q_created"][rows, h],
                                     st["tx_created"])
        st["tx_start"] = jnp.where(go, t0, st["tx_start"])
        if acyclic:
            st["tx_visited"] = jnp.where(go[:, None],
                                         st["q_visited"][rows, h],
                                         st["tx_visited"])
        st["tx_count"] = st["tx_count"] + jnp.sum(go, dtype=jnp.int32)
        st["tx_active"] = st["tx_active"] | go
        st["q_active"] = st["q_active"].at[rows, h].set(
            jnp.where(go, False, st["q_active"][rows, h]))

        # each sender's link rate holds for the epoch
        if sparse:
            dd = jnp.sqrt(jnp.sum(jnp.square(pos - pos[st["tx_dst"]]),
                                  axis=-1) + 1e-9)
            s = snr_at(dd)
            rate = jnp.where((s >= cfg["snr_min_db"]) & (st["tx_dst"] != rows),
                             capacity(s), 1.0)
        else:
            rate = cap[rows, st["tx_dst"]]
        target_cum = cum[jnp.clip(layers, 0, L)]

        def tick_fn(st, j):
            t_now = t0 + (f(j.astype(jnp.float32)) + 1.0) * tick
            return ticked(st, jax.random.fold_in(kt, j), t_now, rate,
                          target_cum, acc_now), None

        st, _ = jax.lax.scan(tick_fn, st, jnp.arange(
            int(round(dp / tick))))
        return st, None

    # --- one tick ---------------------------------------------------------
    def ticked(st, key, t_now, rate, target_cum, acc_now):
        # Markov-modulated arrivals (ON/OFF chain per node)
        k_sw, k_ar = jax.random.split(key)
        on, off = cfg["burst_on_s"], cfg["burst_off_s"]
        p_on_off = 1.0 - jnp.exp(-tick / on)
        p_off_on = 1.0 - jnp.exp(-tick / off)
        duty = on / (on + off)
        p_arr = 1.0 - jnp.exp(-tick / (cfg["task_period_s"] * duty))
        u = jax.random.uniform(k_sw, (n,))
        burst = jnp.where(st["burst_on"], u >= p_on_off, u < p_off_on)
        arrive = jax.random.bernoulli(k_ar, p_arr, (n,)) & burst
        st = enqueue(dict(st, burst_on=burst), arrive, jnp.zeros((n,), ft),
                     jnp.full((n,), t_now, ft), jnp.zeros((n, n), bool))
        st["generated"] = st["generated"] + jnp.sum(arrive, dtype=jnp.int32)

        # compute: a tick's budget may finish one task and start the next
        budget = F * tick
        for _ in range(2):
            h, has = head(st)
            cur = st["q_cum"][rows, h]
            adv = jnp.where(has, jnp.minimum(budget,
                                             jnp.maximum(target_cum - cur,
                                                         0.0)), 0.0)
            new = cur + adv
            done = has & (new >= target_cum - 1e-6)
            lat = t_now - st["q_created"][rows, h]
            st = dict(st)
            st["q_cum"] = st["q_cum"].at[rows, h].set(
                jnp.where(has, new, cur))
            st["proc"] = st["proc"] + adv
            st["e_comp"] = st["e_comp"] + adv * cfg["energy_per_gflop_j"]
            st["done"] = st["done"] + jnp.sum(done, dtype=jnp.int32)
            st["lat_sum"] = st["lat_sum"] + jnp.sum(jnp.where(done, lat, 0.0))
            st["acc_sum"] = st["acc_sum"] + jnp.sum(jnp.where(done, acc_now,
                                                              0.0))
            st["q_active"] = st["q_active"].at[rows, h].set(
                jnp.where(done, False, st["q_active"][rows, h]))
            budget = budget - adv

        # transfers: bits fly at the epoch's rate; a landed transfer is
        # delivered when no lower-numbered sender lands on the same node
        flying = st["tx_active"] & (st["tx_bits"] > 0.0)
        tx_w = 10.0 ** (cfg["tx_power_dbm"] / 10.0) * 1e-3
        st["tx_bits"] = jnp.where(flying, st["tx_bits"] - rate * tick,
                                  st["tx_bits"])
        st["e_tx"] = st["e_tx"] + jnp.where(flying, tx_w * tick, 0.0)
        landed = st["tx_active"] & (st["tx_bits"] <= 0.0)
        first = jnp.full((n,), n, jnp.int32).at[st["tx_dst"]].min(
            jnp.where(landed, rows, n))
        deliver = landed & (first[st["tx_dst"]] == rows)
        receives = first < n
        src = jnp.where(receives, first, 0)
        # a delivered task has also left its sender
        visited = st["tx_visited"][src] | (rows[None, :] == src[:, None]) \
            if acyclic else None
        st = enqueue(st, receives, st["tx_cum"][src], st["tx_created"][src],
                     visited)
        st["tx_active"] = st["tx_active"] & ~deliver
        st["tx_delivered"] = st["tx_delivered"] + jnp.sum(deliver,
                                                          dtype=jnp.int32)
        st["tx_time_sum"] = st["tx_time_sum"] + jnp.sum(
            jnp.where(deliver, t_now - st["tx_start"], 0.0))
        return st

    n_epochs = int(round(cfg["sim_time_s"] / dp))
    st, _ = jax.lax.scan(epoch, st, jnp.arange(n_epochs))

    # --- statistics (paper §5) -------------------------------------------
    done_f = st["done"].astype(ft)
    done = jnp.maximum(done_f, 1.0)
    rem_q = load(st)
    rem_tx = jnp.where(st["tx_active"], total - st["tx_cum"], 0.0)
    x = st["proc"] / F
    e_total = jnp.sum(st["e_comp"] + st["e_tx"])
    tps = done_f / cfg["sim_time_s"]
    acc = st["acc_sum"] / done
    ae = e_total / done
    al = st["lat_sum"] / done
    return {
        "completed": done_f,
        "generated": st["generated"].astype(ft),
        "avg_latency_s": al,
        "avg_accuracy": acc,
        "remaining_gflops": jnp.sum(rem_q) + jnp.sum(rem_tx),
        "avg_transfer_time_s": st["tx_time_sum"] / jnp.maximum(
            st["tx_delivered"].astype(ft), 1.0),
        "transfers": st["tx_count"].astype(ft),
        "transfers_delivered": st["tx_delivered"].astype(ft),
        "jain_fairness": jnp.sum(x) ** 2 / (n * jnp.sum(x * x) + 1e-12),
        "energy_per_task_j": ae,
        "energy_total_j": e_total,
        "throughput_tps": tps,
        "dropped": st["dropped"].astype(ft),
        "fom": tps * acc / jnp.maximum(ae * al, 1e-12),
    }


@functools.lru_cache(maxsize=None)
def _batched(cfg_json: str, strategy: str, dtype_name: str):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)
    return jax.jit(jax.vmap(lambda k: simulate(k, cfg, strategy, dtype)))


def run_keys(keys, cfg: Dict, strategy: str, dtype=jnp.float32):
    """The reference over a batch of run keys [B, 2] -> {stat: [B]}
    (float32 numpy), jitted and vmapped over the runs."""
    fn = _batched(json.dumps(cfg, sort_keys=True), strategy,
                  jnp.dtype(dtype).name)
    out = fn(jnp.asarray(keys))
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


def run_blocks(keys, cfg: Dict, strategy: str, block: int,
               dtype=jnp.float32):
    """``run_keys`` over blocks of ``block`` runs, so that a large swarm
    fits and every call has one shape (the last block is padded with its
    own last key, and the padding is dropped).  Blocks go round the
    devices, all dispatched before any is read."""
    keys = np.asarray(keys)
    devices = jax.devices()
    fn = _batched(json.dumps(cfg, sort_keys=True), strategy,
                  jnp.dtype(dtype).name)
    pending = []
    for b, s in enumerate(range(0, len(keys), block)):
        part = keys[s:s + block]
        pad = np.repeat(part[-1:], block - len(part), axis=0)
        x = jax.device_put(np.concatenate([part, pad]),
                           devices[b % len(devices)])
        pending.append((len(part), fn(x)))
    parts = [{k: np.asarray(v, np.float32)[:m] for k, v in out.items()}
             for m, out in pending]
    return {k: np.concatenate([p[k] for p in parts]) for k in STATS}
