"""Delivery through the node axis' one-hot against the indexed delivery.

On the dense link representation ``transfer.progress`` resolves receiver
contention and moves the delivered fields from origin to destination with
masks over the ``[N destinations, N origins]`` one-hot of ``tx_dst``; on
neighbour lists it scatters over the destination axis and gathers at the
winning origin.  The plain reference here is the indexed ``progress`` as
it was before the one-hot: both representations must give its state bit
for bit — every queue field, counter, trace record and carry — on random
states at N = 30 and N = 33 (two visited words), under forced contention
(every origin to one destination, origins in pairs), with payloads arrived
and in flight, down destinations, fields holding -0.0, traced and not,
one profile and a task mix, and vmapped over runs.

The programs run op by op, not under ``jit``: XLA's CPU compiler lets a
float sum reassociate, so the order of ``tx_time_sum``'s sum over nodes
follows whatever its fusion also loads, the same expression on either
side.  Op by op, each side's sum is the same program on the same inputs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_queue_slots import assert_same
from test_visited_bits import pack

from repro.configs.base import SwarmConfig
from repro.swarm import transfer
from repro.swarm.queues import INT_MAX, node_bits, push
from repro.swarm.simulator import init_state
from repro.trace import record as trace_record

Q = 6


# ---------------------------------------------------------------------------
# the indexed reference
# ---------------------------------------------------------------------------


def ref_progress(st, rate, alive, cfg, t_now):
    """One tick of progress and delivery by index: scatters at ``tx_dst``
    and gathers at the winning origin (``inv``)."""
    n = st["F"].shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    tick = cfg.tick_s
    live = alive & alive[st["tx_dst"]]
    active = st["tx_active"] & live
    pre_arrived = st["tx_bits"] <= 0.0
    flying = active & ~pre_arrived
    tx_w = 10.0 ** (cfg.tx_power_dbm / 10.0) * 1e-3
    st = dict(st)
    if "hop_stall" in st:
        st["hop_stall"] = st["hop_stall"] + (
            st["tx_active"] & (~live | pre_arrived)).astype(jnp.int32)
    st["tx_bits"] = jnp.where(flying, st["tx_bits"] - rate * tick,
                              st["tx_bits"])
    st["e_tx"] = st["e_tx"] + jnp.where(flying, tx_w * tick, 0.0)
    if "tx_energy" in st:
        st["tx_energy"] = st["tx_energy"] + jnp.where(
            flying, tx_w * tick, 0.0)
    arrived = active & (st["tx_bits"] <= 0.0)
    origin_rank = jnp.where(arrived, rows, INT_MAX)
    winner = jnp.full((n,), INT_MAX, jnp.int32).at[st["tx_dst"]].min(
        jnp.where(arrived, origin_rank, INT_MAX))
    deliver = arrived & (winner[st["tx_dst"]] == rows)
    dst_mask = jnp.zeros((n,), bool).at[st["tx_dst"]].max(deliver)
    inv = jnp.full((n,), 0, jnp.int32).at[st["tx_dst"]].max(
        jnp.where(deliver, rows, 0))
    cum_d = st["tx_cum"][inv]
    created_d = st["tx_created"][inv]
    visited_d = st["tx_visited"][:, inv] | node_bits(
        inv, st["tx_visited"].shape[0])
    profile_d = st["tx_profile"][inv] if "tx_profile" in st else None
    if trace_record.hops_enabled(cfg):
        st = trace_record.write_hop_records(
            st, deliver, seq=st["hop_seq"], src=rows, dst=st["tx_dst"],
            t_depart=st["tx_start"], t_arrive=t_now, bits=st["hop_bits"],
            boundary_layer=st["hop_layer"], stall_ticks=st["hop_stall"])
    if trace_record.enabled(cfg):
        st = trace_record.traced_push(
            st, dst_mask, cum_d, created_d, visited_d,
            src=st["tx_src"][inv], energy=st["tx_energy"][inv],
            txtime=st["tx_txtime"][inv] + jnp.where(
                dst_mask, t_now - st["tx_start"][inv], 0.0),
            t_now=t_now, cfg=cfg, profile=profile_d)
    else:
        st = push(st, dst_mask, cum_d, created_d, visited_d,
                  None if profile_d is None else {"profile": profile_d})
    st["tx_active"] = st["tx_active"] & ~deliver
    st["tx_delivered"] = st["tx_delivered"] + jnp.sum(deliver,
                                                      dtype=jnp.int32)
    st["tx_time_sum"] = st["tx_time_sum"] + jnp.sum(
        jnp.where(deliver, t_now - st["tx_start"], 0.0))
    return st


# ---------------------------------------------------------------------------
# seeded states
# ---------------------------------------------------------------------------


def _cfg(n, kind):
    cfg = dataclasses.replace(SwarmConfig(), num_workers=n, queue_slots=Q)
    if kind in ("traced", "mix"):     # capacities unlike any node count
        cfg = dataclasses.replace(cfg, trace_capacity=5 * n * Q,
                                  trace_hop_capacity=3 * n * Q)
    if kind == "mix":
        cfg = dataclasses.replace(cfg, task_profiles=("vgg16", "resnet50"),
                                  task_mix=(0.5, 0.5))
    return cfg


def _signed(rng, lo, hi, shape):
    """Uniform floats with a fifth of them -0.0."""
    x = rng.uniform(lo, hi, shape).astype(np.float32)
    x[rng.random(shape) < 0.2] = -0.0
    return x


def _dst(rng, n, pattern):
    if pattern == "all_to_one":
        return np.full(n, rng.integers(0, n), np.int32)
    if pattern == "pairs":           # origins 2k and 2k + 1 to one node
        return rng.permutation(n)[np.arange(n) // 2].astype(np.int32)
    return rng.integers(0, n, n).astype(np.int32)


def _state(n, seed, kind, pattern="random", bits="random", alive="up"):
    """A state mid-run, its fields random; ``bits`` picks the payloads
    (``arrived``: all at or below zero, ``flying``: none), ``alive`` the
    fault mask (``down``: a third of the nodes, the shared destination of
    ``all_to_one`` included)."""
    cfg = _cfg(n, kind)
    rng = np.random.default_rng(seed)
    st = dict(init_state(jax.random.PRNGKey(seed), cfg, n))
    dst = _dst(rng, n, pattern)
    payload = {"arrived": rng.choice([0.0, -0.0, -5.0], n),
               "flying": rng.uniform(1e3, 1e9, n),
               "random": np.where(rng.random(n) < 0.5, 0.0,
                                  rng.uniform(1.0, 300.0, n))}[bits]
    st.update(
        tx_active=jnp.asarray(rng.random(n) < 0.8),
        tx_dst=jnp.asarray(dst),
        tx_bits=jnp.asarray(payload, jnp.float32),
        tx_cum=jnp.asarray(_signed(rng, 0.0, 12.0, n)),
        tx_created=jnp.asarray(_signed(rng, 0.0, 9.0, n)),
        tx_start=jnp.asarray(_signed(rng, 0.0, 9.0, n)),
        tx_visited=pack(rng.random((n, n)) < 0.3),
        q_active=jnp.asarray(rng.random((n, Q)) < 0.5),
        q_cum=jnp.asarray(_signed(rng, 0.0, 12.0, (n, Q))),
        q_seq=jnp.asarray(rng.permutation(n * Q).reshape(n, Q), jnp.int32),
        seq_counter=jnp.int32(n * Q),
        q_visited=pack(rng.random((n, Q, n)) < 0.3),
        e_tx=jnp.asarray(_signed(rng, 0.0, 2.0, n)),
        tx_delivered=jnp.int32(rng.integers(0, 50)),
        tx_time_sum=jnp.float32(rng.uniform(0.0, 9.0)))
    st["q_active"] = st["q_active"].at[0].set(True)        # one full queue
    if kind in ("traced", "mix"):
        st.update(
            tx_src=jnp.asarray(rng.integers(0, n, n), jnp.int32),
            tx_energy=jnp.asarray(_signed(rng, 0.0, 3.0, n)),
            tx_txtime=jnp.asarray(_signed(rng, 0.0, 2.0, n)),
            hop_seq=jnp.asarray(rng.permutation(n), jnp.int32),
            hop_bits=jnp.asarray(rng.uniform(0, 1e6, n), jnp.float32),
            hop_layer=jnp.asarray(rng.integers(0, 16, n), jnp.int32),
            hop_stall=jnp.asarray(rng.integers(0, 4, n), jnp.int32))
    if kind == "mix":
        st.update(tx_profile=jnp.asarray(rng.integers(0, 2, n), jnp.int32),
                  q_profile=jnp.asarray(rng.integers(0, 2, (n, Q)),
                                        jnp.int32))
    up = np.ones(n, bool)
    if alive == "down":
        up[rng.random(n) < 0.33] = False
        up[dst[0]] = False
    rate = jnp.asarray(rng.uniform(0.0, 2e4, n), jnp.float32)
    return st, cfg, rate, jnp.asarray(up)


T_NOW = jnp.float32(9.25)


def _one(st, rate, alive, cfg, dense):
    if dense is None:
        return ref_progress(st, rate, alive, cfg, T_NOW)
    return transfer.progress(st, rate, alive, cfg, T_NOW, dense=dense)


# ---------------------------------------------------------------------------
# equality with the reference
# ---------------------------------------------------------------------------

KINDS = ("plain", "traced", "mix")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("alive", ["up", "down"])
@pytest.mark.parametrize("bits", ["random", "arrived", "flying"])
@pytest.mark.parametrize("pattern", ["random", "all_to_one", "pairs"])
@pytest.mark.parametrize("n", [30, 33])
def test_dense_progress_matches_indexed(n, pattern, bits, alive, kind):
    st, cfg, rate, up = _state(n, 100 + n, kind, pattern, bits, alive)
    want = _one(st, rate, up, cfg, None)
    assert_same(_one(st, rate, up, cfg, True), want)
    arrived = np.asarray(st["tx_active"] & up & up[st["tx_dst"]]
                         & (want["tx_bits"] <= 0.0))
    delivered = int(want["tx_delivered"] - st["tx_delivered"])
    if bits == "flying" or (pattern == "all_to_one" and alive == "down"):
        assert delivered == 0
    elif pattern == "all_to_one" and bits == "arrived":
        assert delivered == 1         # the lowest arrived origin alone
    assert delivered == len(set(np.asarray(st["tx_dst"])[arrived]))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [30, 33])
def test_lists_progress_matches_indexed(n, kind):
    """The neighbour-list path keeps the indexed delivery."""
    for pattern in ("random", "all_to_one", "pairs"):
        st, cfg, rate, up = _state(n, 200 + n, kind, pattern, "random",
                                   "down")
        assert_same(_one(st, rate, up, cfg, False),
                    _one(st, rate, up, cfg, None))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [30, 33])
def test_vmap_over_runs_matches_indexed(n, kind):
    """Three runs with different states, as the executor vmaps them, over
    two ticks (the first delivery's losers win on the second)."""
    runs = [_state(n, seed, kind, pattern, "random", alive)
            for seed, pattern, alive in ((1, "random", "up"),
                                         (2, "all_to_one", "up"),
                                         (3, "pairs", "down"))]
    cfg = runs[0][1]
    batch = [jax.tree.map(lambda *xs: jnp.stack(xs), *[r[i] for r in runs])
             for i in (0, 2, 3)]

    def two_ticks(dense):
        def one(st, rate, alive):
            for t in (T_NOW, T_NOW + cfg.tick_s):
                st = (ref_progress(st, rate, alive, cfg, t) if dense is None
                      else transfer.progress(st, rate, alive, cfg, t,
                                             dense=dense))
            return st
        return jax.vmap(one)(*batch)

    want = two_ticks(None)
    assert_same(two_ticks(True), want)
    assert_same(two_ticks(False), want)
