"""Plain reference of the swarm simulator under a task mix: each task is a
split inference of one of several published networks, drawn at arrival.

Written from the paper's model (arXiv:2503.16146 §3, §5, Eqs. 3-4, 9-16) and
from the two networks' layer tables alone; it imports nothing of the
program under test, and from ``bench/reference/swarm.py`` only the statistic
names, strategy ids, the supported-scenario check and its ``cnn60`` profile.

* **VGG-16**, configuration D (arXiv:1409.1556, Table 1): a unit per
  convolution (a block's 2×2 max-pool belongs to its last convolution) and
  per fully connected layer, 16 units.
* **ResNet-50** (arXiv:1512.03385, Table 1, v1: a stage's stride on its
  first 1×1): the stem (conv1 and the max-pool), one unit per bottleneck
  block (the projection shortcut counted in each stage's first block), and
  the head (average pool and fc1000), 18 units.

Both take a 224×224 uint8 RGB input and pass float32 activations; a unit's
GFLOPs are 2 × its multiply-accumulates.  A task is offloaded at its last
whole unit, shipping the activation there; it completes at its network's
full depth.  The same random draws as the swarm reference, plus one per
node and tick for the profile of an arrival: a uniform draw from
``fold_in(tick key, PROFILE_KEY)`` against the cumulative shares.

Supported: circular mobility, two-ray channel, no faults, dense links, the
Distributed (4), RandomAcyclic (2) and LocalOnly (0) strategies, early exit
off.  Anything else raises.  ``dtype`` is as in the swarm reference:
float32 is the configuration's, bfloat16 the control.
"""
from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.swarm import (INT_MAX, STATS as BASE_STATS, STRATEGIES,
                                   _check_supported, task_profile)

BIG = 1e30
NEG = -1e30
PROFILE_KEY = 0x5EED
INPUT_BYTES = 224 * 224 * 3
FLOAT_BYTES = 4

# VGG-16 D, Table 1: (name, spatial size the convolution runs at, input
# channels, output channels, kernel, spatial size after the unit)
VGG16_D = [
    ("conv1_1", 224, 3, 64, 3, 224), ("conv1_2+pool", 224, 64, 64, 3, 112),
    ("conv2_1", 112, 64, 128, 3, 112), ("conv2_2+pool", 112, 128, 128, 3, 56),
    ("conv3_1", 56, 128, 256, 3, 56), ("conv3_2", 56, 256, 256, 3, 56),
    ("conv3_3+pool", 56, 256, 256, 3, 28),
    ("conv4_1", 28, 256, 512, 3, 28), ("conv4_2", 28, 512, 512, 3, 28),
    ("conv4_3+pool", 28, 512, 512, 3, 14),
    ("conv5_1", 14, 512, 512, 3, 14), ("conv5_2", 14, 512, 512, 3, 14),
    ("conv5_3+pool", 14, 512, 512, 3, 7),
    ("fc6", 1, 7 * 7 * 512, 4096, 1, 1), ("fc7", 1, 4096, 4096, 1, 1),
    ("fc8", 1, 4096, 1000, 1, 1)]

# ResNet-50, Table 1: stage -> (output size, [(kernel, channels)] of one
# bottleneck, blocks)
RESNET50 = {"conv2_x": (56, [(1, 64), (3, 64), (1, 256)], 3),
            "conv3_x": (28, [(1, 128), (3, 128), (1, 512)], 4),
            "conv4_x": (14, [(1, 256), (3, 256), (1, 1024)], 6),
            "conv5_x": (7, [(1, 512), (3, 512), (1, 2048)], 3)}


def vgg16_table():
    """[(MACs, activation bytes after the unit)] of VGG-16 D."""
    return [(at * at * cin * cout * k * k, out * out * cout * FLOAT_BYTES)
            for _, at, cin, cout, k, out in VGG16_D]


def resnet50_table():
    """[(MACs, activation bytes after the unit)] of ResNet-50 v1."""
    # conv1: 7x7, 64, stride 2 at 112x112; the 3x3/2 max-pool leaves 56x56
    units = [(112 * 112 * 3 * 64 * 7 * 7, 56 * 56 * 64 * FLOAT_BYTES)]
    channels = 64
    for size, convs, blocks in RESNET50.values():
        for b in range(blocks):
            mac, c_in = 0, channels
            for k, c_out in convs:
                mac += size * size * c_in * c_out * k * k
                c_in = c_out
            if b == 0:                       # projection shortcut (1x1)
                mac += size * size * channels * c_in
            channels = c_in
            units.append((mac, size * size * channels * FLOAT_BYTES))
    units.append((channels * 1000, 1000 * FLOAT_BYTES))   # avg pool, fc1000
    return units


def network_profile(table):
    """cum GFLOPs [L+1], activation bits [L+1] (input first), bits per
    GFLOP (mean bits at a boundary over mean GFLOPs of a unit), total."""
    macs = np.array([m for m, _ in table], np.int64)
    gflops = 2 * macs / 1e9
    cum = np.concatenate([[0.0], 2 * np.cumsum(macs) / 1e9])
    bits = 8.0 * np.array([INPUT_BYTES] + [b for _, b in table], np.float64)
    return cum, bits, float(bits[1:].mean()) / float(gflops.mean()), \
        float(cum[-1])


def profiles(cfg: Dict):
    """The configured profiles as numpy tables: cum GFLOPs [P, W] (each row
    padded with its total), activation bits [P, W] (padded with the last),
    units [P], bits per GFLOP [P], totals [P]."""
    out = []
    for name in cfg["task_profiles"]:
        if name == "vgg16":
            out.append(network_profile(vgg16_table()))
        elif name == "resnet50":
            out.append(network_profile(resnet50_table()))
        elif name == "cnn60":
            cum, bits, bpg = task_profile(cfg)
            out.append((cum, bits, bpg, float(cfg["task_gflops_total"])))
        else:
            raise NotImplementedError(f"no layer table for {name!r}")
    units = np.array([len(c) - 1 for c, *_ in out], np.int32)
    W = int(units.max()) + 1
    cum = np.stack([np.pad(c, (0, W - len(c)), mode="edge")
                    for c, *_ in out]).astype(np.float32)
    bits = np.stack([np.pad(b, (0, W - len(b)), mode="edge")
                     for _, b, *_ in out]).astype(np.float32)
    bpg = [b for _, _, b, _ in out]
    totals = np.array([t for *_, t in out], np.float32)
    return cum, bits, units, bpg, totals


def stats_of(cfg: Dict):
    """The compared statistics: the swarm's 14 and the completions of each
    profile."""
    return BASE_STATS + tuple(f"completed_{p}" for p in cfg["task_profiles"])


def simulate(key, cfg: Dict, strategy: str, dtype=jnp.float32):
    """One run; returns the statistics as scalars of ``dtype``."""
    _check_supported(cfg, strategy)
    if cfg["neighbor_mode"] != "dense" or cfg["early_exit_enabled"]:
        raise NotImplementedError("the mix reference models dense links "
                                  "with early exit off")
    ft = dtype
    n = cfg["num_workers"]
    Q = cfg["queue_slots"]
    acyclic = strategy == "RandomAcyclic"
    names = cfg["task_profiles"]
    P = len(names)
    shares = np.asarray(cfg["task_mix"], np.float64)
    cut = jnp.asarray(np.cumsum(shares)[:-1].astype(np.float32))
    cum_np, bits_np, units_np, bpg_list, totals_np = profiles(cfg)
    cum = jnp.asarray(cum_np).astype(ft)
    act_bits = jnp.asarray(bits_np).astype(ft)
    units = jnp.asarray(units_np)
    done_at = cum[jnp.arange(P), units]
    bpg = jnp.asarray(np.array(bpg_list, np.float32)).astype(ft)
    bpg_idle = sum(s * b for s, b in zip(cfg["task_mix"], bpg_list))
    totals = jnp.asarray(totals_np).astype(ft)
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
        q_profile=jnp.zeros((n, Q), jnp.int32),
        seq_counter=jnp.int32(0),
        tx_active=jnp.zeros((n,), bool), tx_dst=jnp.zeros((n,), jnp.int32),
        tx_bits=jnp.zeros((n,), ft), tx_cum=jnp.zeros((n,), ft),
        tx_created=jnp.zeros((n,), ft), tx_start=jnp.zeros((n,), ft),
        tx_profile=jnp.zeros((n,), jnp.int32),
        phi=F, cong_prev=jnp.zeros((n,), ft), cong_D=jnp.zeros((n,), ft),
        burst_on=jnp.zeros((n,), bool),
        done=jnp.int32(0), done_p=jnp.zeros((P,), jnp.int32),
        lat_sum=jnp.zeros((), ft),
        acc_sum=jnp.zeros((), ft), proc=jnp.zeros((n,), ft),
        e_comp=jnp.zeros((n,), ft), e_tx=jnp.zeros((n,), ft),
        tx_count=jnp.int32(0), tx_delivered=jnp.int32(0),
        tx_time_sum=jnp.zeros((), ft), dropped=jnp.int32(0),
        generated=jnp.int32(0))
    if acyclic:
        st.update(q_visited=jnp.zeros((n, Q, n), bool),
                  tx_visited=jnp.zeros((n, n), bool))

    # --- helpers ----------------------------------------------------------
    def head(st):
        """FIFO head: the active slot with the lowest sequence number."""
        seqv = jnp.where(st["q_active"], st["q_seq"], INT_MAX)
        return jnp.argmin(seqv, axis=1), jnp.any(st["q_active"], axis=1)

    def load(st):
        """T_i: GFLOPs still to run over every queued task, each against
        its own network's total."""
        rem = jnp.maximum(totals[st["q_profile"]] - st["q_cum"], 0.0)
        return jnp.sum(jnp.where(st["q_active"], rem, 0.0), axis=1)

    def enqueue(st, mask, cum_v, created_v, profile_v, visited_v=None):
        """One task into the first free slot of every masked node; a node
        with no free slot drops it."""
        free = jnp.argmin(st["q_active"], axis=1)
        has_free = ~jnp.all(st["q_active"], axis=1)
        ok = mask & has_free
        seq = st["seq_counter"] + jnp.cumsum(ok.astype(jnp.int32),
                                             dtype=jnp.int32) - 1
        st = dict(st)
        for name, v in (("q_active", True), ("q_cum", cum_v),
                        ("q_created", created_v), ("q_seq", seq),
                        ("q_profile", profile_v)):
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

    def boundary_layer(cum_done, p):
        """Last whole unit of network ``p`` reached: unit work is lost on
        offload."""
        lyr = jnp.sum(cum[p] <= cum_done[:, None], axis=1) - 1
        return jnp.clip(lyr, 0, units[p])

    def capacity(snr):
        return cfg["bandwidth_hz"] * jnp.log2(1.0 + jnp.power(10.0,
                                                              snr / 10.0))

    def snr_at(dist):
        pl = 40.0 * jnp.log10(jnp.maximum(dist, 1.0)) - 20.0 * jnp.log10(
            f(cfg["altitude_m"] * cfg["altitude_m"]))
        return cfg["tx_power_dbm"] - pl - cfg["noise_dbm"]

    # --- one epoch --------------------------------------------------------
    def epoch(st, i):
        key_e = jax.random.fold_in(k_run, i)
        t0 = f(i.astype(jnp.float32)) * dp
        kd, kt = jax.random.split(key_e)
        ang = phase0 + omega * t0
        pos = center + cfg["movement_radius_m"] * jnp.stack(
            [jnp.cos(ang), jnp.sin(ang)], axis=-1)
        T = load(st)

        # links (Eqs. 3, 4, 9) and the diffusive metric (Eq. 10); the
        # delay per GFLOP is that of the head task's network (the mix's
        # mean where the queue is empty)
        inv_phi = 1.0 / st["phi"]
        diff = pos[:, None, :] - pos[None, :, :]
        dist = jnp.sqrt(jnp.sum(jnp.square(diff), axis=-1) + 1e-9)
        snr = snr_at(dist)
        adj = (snr >= cfg["snr_min_db"]) & ~jnp.eye(n, dtype=bool)
        cap = jnp.where(adj, capacity(snr), 1.0)
        h, has = head(st)
        per_gflop = jnp.where(has, bpg[st["q_profile"][rows, h]],
                              f(bpg_idle))
        d_tx = jnp.where(adj, per_gflop[:, None] / cap, BIG)
        worst = jnp.max(jnp.where(adj, d_tx + inv_phi[None, :], NEG), axis=1)
        deg = jnp.sum(adj, axis=1)
        inv_new = (1.0 / F + worst) / (deg.astype(ft) + 1.0)
        phi = jnp.where(deg > 0, 1.0 / inv_new, F)

        # decision (Eqs. 11-13): offload to the least utilized neighbor
        U = T / jnp.maximum(phi, 1e-9)
        cand = jnp.where(adj, U[None, :], BIG)
        has_nbr = deg > 0
        target = jnp.where(has_nbr, jnp.argmin(cand, axis=1), -1)
        do = has_nbr & ((U - jnp.min(cand, axis=1)) > cfg["gamma"])
        if strategy == "LocalOnly":
            do = jnp.zeros((n,), bool)
        elif acyclic:
            k3 = jax.random.split(kd, 3)[2]
            fresh = adj & ~st["q_visited"][rows, h]
            score = jnp.where(fresh, jax.random.gumbel(k3, adj.shape), NEG)
            target = jnp.argmax(score, axis=1)
            do = jax.random.bernoulli(jax.random.fold_in(k3, 1),
                                      cfg["random_acyclic_p"], (n,)) & \
                jnp.any(fresh, axis=1)

        # congestion (Eq. 15); early exit is off, every task runs whole
        D = st["cong_D"] + cfg["ema_alpha"] * (
            (T - st["cong_prev"]) / dp - st["cong_D"])
        acc_now = f(cfg["exit_accuracy"][2])
        st = dict(st, phi=phi, cong_prev=T, cong_D=D)

        # start transfers: the head task leaves at its last unit boundary,
        # shipping its own network's activation there
        go = do & has & ~st["tx_active"] & (target >= 0)
        cum_h = st["q_cum"][rows, h]
        p_h = st["q_profile"][rows, h]
        lyr = boundary_layer(cum_h, p_h)
        st["tx_dst"] = jnp.where(go, target, st["tx_dst"])
        st["tx_bits"] = jnp.where(go, act_bits[p_h, lyr], st["tx_bits"])
        st["tx_cum"] = jnp.where(go, cum[p_h, lyr], st["tx_cum"])
        st["tx_profile"] = jnp.where(go, p_h, st["tx_profile"])
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

        rate = cap[rows, st["tx_dst"]]

        def tick_fn(st, j):
            t_now = t0 + (f(j.astype(jnp.float32)) + 1.0) * tick
            return ticked(st, jax.random.fold_in(kt, j), t_now, rate,
                          acc_now), None

        st, _ = jax.lax.scan(tick_fn, st, jnp.arange(
            int(round(dp / tick))))
        return st, None

    # --- one tick ---------------------------------------------------------
    def ticked(st, key, t_now, rate, acc_now):
        # Markov-modulated arrivals (ON/OFF chain per node); each arrival's
        # network drawn with the configured shares
        k_sw, k_ar = jax.random.split(key)
        on, off = cfg["burst_on_s"], cfg["burst_off_s"]
        p_on_off = 1.0 - jnp.exp(-tick / on)
        p_off_on = 1.0 - jnp.exp(-tick / off)
        duty = on / (on + off)
        p_arr = 1.0 - jnp.exp(-tick / (cfg["task_period_s"] * duty))
        u = jax.random.uniform(k_sw, (n,))
        burst = jnp.where(st["burst_on"], u >= p_on_off, u < p_off_on)
        arrive = jax.random.bernoulli(k_ar, p_arr, (n,)) & burst
        draw = jax.random.uniform(jax.random.fold_in(key, PROFILE_KEY), (n,))
        which = jnp.sum(cut[None, :] <= draw[:, None], axis=1,
                        dtype=jnp.int32)
        st = enqueue(dict(st, burst_on=burst), arrive, jnp.zeros((n,), ft),
                     jnp.full((n,), t_now, ft), which,
                     jnp.zeros((n, n), bool))
        st["generated"] = st["generated"] + jnp.sum(arrive, dtype=jnp.int32)

        # compute: a tick's budget may finish one task and start the next;
        # a task is done at its network's full depth
        budget = F * tick
        for _ in range(2):
            h, has = head(st)
            cur = st["q_cum"][rows, h]
            p_h = st["q_profile"][rows, h]
            target_cum = done_at[p_h]
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
            st["done_p"] = st["done_p"].at[p_h].add(done.astype(jnp.int32))
            st["lat_sum"] = st["lat_sum"] + jnp.sum(jnp.where(done, lat, 0.0))
            st["acc_sum"] = st["acc_sum"] + jnp.sum(jnp.where(done, acc_now,
                                                              0.0))
            st["q_active"] = st["q_active"].at[rows, h].set(
                jnp.where(done, False, st["q_active"][rows, h]))
            budget = budget - adv

        # transfers: bits fly at the epoch's rate; a landed transfer is
        # delivered when no lower-numbered sender lands on the same node,
        # and the task keeps its network
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
        visited = st["tx_visited"][src] | (rows[None, :] == src[:, None]) \
            if acyclic else None
        st = enqueue(st, receives, st["tx_cum"][src], st["tx_created"][src],
                     st["tx_profile"][src], visited)
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
    rem_tx = jnp.where(st["tx_active"],
                       totals[st["tx_profile"]] - st["tx_cum"], 0.0)
    x = st["proc"] / F
    e_total = jnp.sum(st["e_comp"] + st["e_tx"])
    tps = done_f / cfg["sim_time_s"]
    acc = st["acc_sum"] / done
    ae = e_total / done
    al = st["lat_sum"] / done
    out = {
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
    for p, name in enumerate(names):
        out[f"completed_{name}"] = st["done_p"][p].astype(ft)
    return out


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
    """``run_keys`` over blocks of ``block`` runs (the last padded with its
    own last key, the padding dropped), round the devices, all dispatched
    before any is read."""
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
    return {k: np.concatenate([p[k] for p in parts]) for k in stats_of(cfg)}
