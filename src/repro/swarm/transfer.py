"""Single-outgoing-transfer machinery (paper §3.2, DESIGN.md §3.3).

Each node carries at most one in-flight outgoing task transfer.  An epoch
decision *initiates* a transfer (pop the FIFO head, snap its progress back
to the last layer boundary per §3.1, ship the boundary activation bits);
fine ticks *progress* it at the epoch-frozen link capacity and *deliver* it
into the destination queue — one delivery per receiver per tick, lowest
origin index winning contention.

Delivery moves the winning origin's fields to its destination, and how
depends on the epoch's link representation.  Dense, it runs through the
``[N, N]`` one-hot of each origin's destination (``_deliver_one_hot``):
masks and reductions that fuse into a few vector ops, where an indexed
scatter or gather per node runs as a serial loop on the TPU.  Over
neighbour lists it scatters at the destinations and gathers at the winning
origins (``_deliver_indexed``), O(N) per tick.  The choice follows the
representation and not N: the one-hot costs N compares per origin, so the
serial loop wins only beyond N ≈ 10⁴, and a dense swarm that large
(``[runs, N, N]`` per epoch) does not fit one chip.

Accounting note: a transfer whose payload has fully arrived
(``tx_bits <= 0``) but that lost receiver contention stays ``tx_active``
until it wins a delivery slot.  Those waiting ticks are *queue-wait*, not
airtime — the radio is done — so bit decrement and transmit-energy accrual
freeze once ``tx_bits <= 0`` (they used to keep running, over-counting
``e_tx`` and the task's ``tx_energy`` for every contended delivery).
Under hop capture the waiting ticks are counted in ``hop_stall`` instead,
alongside endpoint-down fault stalls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import SwarmConfig
from repro.obs.scopes import phase
from repro.swarm.queues import (INT_MAX, head_slot, head_visited,
                                node_bits, pop_head, push, slot_mask,
                                slot_read)
from repro.swarm.tasks import boundary_bits, layer_of, snap_to_boundary
from repro.trace import record as trace_record


def initiate(st, elig, tgt, t0, profile):
    """Start transfers where ``elig``: pop the head task, discard partial-
    layer progress and stage the boundary activation for shipping (of the
    task's own profile, which travels with it, under a task mix)."""
    Q = st["q_active"].shape[1]
    head, _ = head_slot(st)
    at_head = slot_mask(head, Q)
    cum_h = slot_read(st["q_cum"], at_head)
    pid_h = None
    st = dict(st)
    if "q_profile" in st:
        with phase("task_profile"):
            pid_h = slot_read(st["q_profile"], at_head)
            st["tx_profile"] = jnp.where(elig, pid_h, st["tx_profile"])
    cum_snap = snap_to_boundary(profile, cum_h, pid_h)
    bits = boundary_bits(profile, cum_h, pid_h)
    if "tx_src" in st:       # trace attribution rides along (DESIGN §10.2)
        with phase("trace_capture"):
            for f in ("src", "energy", "txtime"):
                st[f"tx_{f}"] = jnp.where(
                    elig, slot_read(st[f"q_{f}"], at_head), st[f"tx_{f}"])
    if "hop_seq" in st:      # hop stream: assign seqs at initiation (§10.5)
        with phase("trace_capture"):
            # i32-pinned reductions: numpy-style widening to i64 under x64
            # would drift the hop-seq carry dtype (swarmlint J002)
            hseq = st["hop_counter"] + jnp.cumsum(
                elig.astype(jnp.int32), dtype=jnp.int32) - 1
            st["hop_seq"] = jnp.where(elig, hseq, st["hop_seq"])
            st["hop_counter"] = st["hop_counter"] + jnp.sum(
                elig.astype(jnp.int32), dtype=jnp.int32)
            st["hop_bits"] = jnp.where(elig, bits, st["hop_bits"])
            st["hop_layer"] = jnp.where(
                elig, jnp.clip(layer_of(profile, cum_h, pid_h), 0,
                               profile.cum_gflops.shape[-1] - 1),
                st["hop_layer"])
            st["hop_stall"] = jnp.where(elig, 0, st["hop_stall"])
    st["tx_dst"] = jnp.where(elig, tgt, st["tx_dst"])
    st["tx_bits"] = jnp.where(elig, bits, st["tx_bits"])
    st["tx_cum"] = jnp.where(elig, cum_snap, st["tx_cum"])
    st["tx_created"] = jnp.where(elig, slot_read(st["q_created"], at_head),
                                 st["tx_created"])
    with phase("visited"):
        st["tx_visited"] = jnp.where(elig, head_visited(st["q_visited"],
                                                        at_head),
                                     st["tx_visited"])
    st["tx_start"] = jnp.where(elig, t0, st["tx_start"])
    # i32 count: exact under any reduction order, so the in-scan sum
    # cannot drift across executor backends (swarmlint J001, §8.2)
    st["tx_count"] = st["tx_count"] + jnp.sum(elig, dtype=jnp.int32)
    st["tx_active"] = st["tx_active"] | elig
    return pop_head(st, elig)


def _deliver_indexed(dst, arrived):
    """Contention and the origin-to-destination move by index: scatters
    over the destination axis and gathers at the winning origin, O(N) per
    tick — the neighbour-list path, where N reaches 10⁴ and more.

    Returns ``(deliver [N] per origin, dst_mask [N] per destination,
    read)``, ``read(x)`` moving an origin field ``[..., N]`` to the
    destinations (origin 0's value where no delivery lands).
    """
    n = dst.shape[0]
    # i32 pin: the origin ranks scatter into i32 contention fields, and
    # default arange/full are i64 under x64 (swarmlint J002)
    rows = jnp.arange(n, dtype=jnp.int32)
    # oob: dst holds node ids from the decision stage, always in [0, N);
    # drop mode is the .at[] default, never exercised (J003)
    winner = jnp.full((n,), INT_MAX, jnp.int32).at[dst].min(
        jnp.where(arrived, rows, INT_MAX))
    deliver = arrived & (winner[dst] == rows)
    # oob: in-range dst, see winner scatter above (J003)
    dst_mask = jnp.zeros((n,), bool).at[dst].max(deliver)
    # oob: in-range dst, see winner scatter above (J003)
    inv = jnp.full((n,), 0, jnp.int32).at[dst].max(
        jnp.where(deliver, rows, 0))                        # origin per dst
    return deliver, dst_mask, lambda x: x[..., inv]


def _arrivals(dst):
    """``bool [N destinations, N origins]``: ``[j, i]`` where origin ``i``
    sends to ``j`` — the transfers' one-hot over the node axis."""
    return jnp.arange(dst.shape[0], dtype=dst.dtype)[:, None] == dst[None, :]


def _deliver_one_hot(dst, arrived):
    """``_deliver_indexed``'s rule and outputs through ``_arrivals``: masks
    and reductions over an ``[N, N]`` pair of node axes, which fuse, where
    an indexed scatter or gather per node runs as a serial loop on the
    TPU — the dense path, which holds ``[N, N]`` per run anyway.

    ``read(x)`` gives ``x`` at each destination's delivering origin bit
    for bit (``slot_read`` over the origins), and the reduction's
    identity where no delivery lands; every consumer gates those lanes by
    ``dst_mask``.
    """
    rows = jnp.arange(dst.shape[0], dtype=jnp.int32)
    # an arrived origin loses to any lower-index arrived origin that has
    # the same destination: the lowest origin index wins
    beaten = jnp.any((dst[:, None] == dst[None, :]) & arrived[None, :]
                     & (rows[None, :] < rows[:, None]), axis=1)
    deliver = arrived & ~beaten
    take = _arrivals(dst) & deliver[None, :]   # ≤ 1 origin per destination
    words = jax.vmap(slot_read, in_axes=(0, None))

    def read(x):
        return slot_read(x, take) if x.ndim == 1 else words(x, take)
    return deliver, jnp.any(take, axis=1), read


def progress(st, rate, alive, cfg: SwarmConfig, t_now, *, dense: bool):
    """One tick of transfer progress + delivery.

    ``rate`` ``[N]`` is each node's epoch-frozen link rate toward its
    transfer destination in bit/s (``neighbors.epoch_links``' ``rate_to``)
    — resolved once per epoch, because tx_dst only changes at epoch
    decisions, never mid-tick.  ``alive`` is the epoch fault mask — a
    transfer whose endpoint is down stalls (bits conserved) and resumes
    when the node recovers.  ``dense`` is the epoch's link representation
    (``Neighborhood.ids is None``): it moves deliveries through the node
    axis' one-hot (``_deliver_one_hot``), neighbour lists by index
    (``_deliver_indexed``).
    """
    n = st["F"].shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    dst = st["tx_dst"]
    tick = cfg.tick_s
    if dense:
        alive_dst = jnp.any(_arrivals(dst) & alive[:, None], axis=0)
    else:
        alive_dst = alive[dst]
    live = alive & alive_dst
    active = st["tx_active"] & live
    # a fully-arrived payload is off the air: no further bit decrement or
    # transmit-energy accrual while it waits out receiver contention
    pre_arrived = st["tx_bits"] <= 0.0
    flying = active & ~pre_arrived
    tx_w = 10.0 ** (cfg.tx_power_dbm / 10.0) * 1e-3
    st = dict(st)
    if "hop_stall" in st:    # pending but not progressing: fault stall or
        with phase("trace_capture"):          # post-arrival queue-wait
            st["hop_stall"] = st["hop_stall"] + (
                st["tx_active"] & (~live | pre_arrived)).astype(jnp.int32)
    st["tx_bits"] = jnp.where(flying, st["tx_bits"] - rate * tick,
                              st["tx_bits"])
    st["e_tx"] = st["e_tx"] + jnp.where(flying, tx_w * tick, 0.0)
    if "tx_energy" in st:    # attribute the airtime joules to the task
        with phase("trace_capture"):
            st["tx_energy"] = st["tx_energy"] + jnp.where(
                flying, tx_w * tick, 0.0)
    arrived = active & (st["tx_bits"] <= 0.0)
    # receiver contention (lowest origin index wins per destination), and
    # the in-flight fields moved to the destination rows
    deliver, dst_mask, read = (_deliver_one_hot if dense
                               else _deliver_indexed)(dst, arrived)
    cum_d = read(st["tx_cum"])
    created_d = read(st["tx_created"])
    with phase("visited"):          # the origin's set, plus the origin
        visited_d = read(st["tx_visited"]
                         | node_bits(rows, st["tx_visited"].shape[0]))
    profile_d = None
    if "tx_profile" in st:
        with phase("task_profile"):
            profile_d = read(st["tx_profile"])
    if trace_record.hops_enabled(cfg):
        st = trace_record.write_hop_records(
            st, deliver, seq=st["hop_seq"], src=rows, dst=dst,
            t_depart=st["tx_start"], t_arrive=t_now, bits=st["hop_bits"],
            boundary_layer=st["hop_layer"], stall_ticks=st["hop_stall"])
    if trace_record.enabled(cfg):
        st = trace_record.traced_push(
            st, dst_mask, cum_d, created_d, visited_d,
            src=read(st["tx_src"]), energy=read(st["tx_energy"]),
            txtime=read(st["tx_txtime"] + (t_now - st["tx_start"])),
            t_now=t_now, cfg=cfg, profile=profile_d)
    else:
        st = push(st, dst_mask, cum_d, created_d, visited_d,
                  None if profile_d is None else {"profile": profile_d})
    st["tx_active"] = st["tx_active"] & ~deliver
    # i32 count (see tx_count in initiate); tx_time_sum below stays a
    # float accumulator and is baselined under J001 with its rationale
    st["tx_delivered"] = st["tx_delivered"] + jnp.sum(deliver,
                                                      dtype=jnp.int32)
    st["tx_time_sum"] = st["tx_time_sum"] + jnp.sum(
        jnp.where(deliver, t_now - st["tx_start"], 0.0))
    return st
