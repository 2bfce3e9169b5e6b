"""In-scan TaskRecord + HopRecord capture (DESIGN.md §10.2, §10.5).

A fixed-capacity record buffer rides in the simulator's scan carry; every
task completion (and queue-full drop) scatters one :mod:`schema` row into
it, keyed by the task's global sequence number from ``swarm/queues.py``.
Because each seq finishes exactly once, slot ``seq`` is written at most
once — the scatter is order-independent, so records are bit-identical
across ``vmap`` / ``shard_map`` / ``lax.map`` executor backends.  Records
whose seq exceeds the capacity are *dropped from capture* (out-of-bounds
scatter with ``mode="drop"``) and counted in a saturating overflow
counter: the buffer never wraps, decode is unambiguous, and
``trace_overflow`` tells you exactly how many task records were lost —
size ``SwarmConfig.trace_capacity`` above the expected task count to
capture everything.  No host callbacks anywhere: the whole path jits.

Attribution state carried alongside the queues (all trace-only — absent
when ``trace_capacity == 0``):

  * ``q_src`` / ``q_energy`` / ``q_txtime`` — per queue slot: generating
    node, energy attributed so far (compute J + transfer J), cumulative
    time in flight;
  * ``tx_src`` / ``tx_energy`` / ``tx_txtime`` — the same, for the
    in-flight outgoing transfer of each node.

The hop stream (``SwarmConfig.trace_hop_capacity``) is the same design a
level down: one row per *delivered transfer*, keyed by a dedicated hop
sequence counter assigned at ``transfer.initiate`` — each hop delivers at
most once, so the scatter is again order-independent.  It is gated
independently of the task stream (either can be on without the other)
and carries its own per-node in-flight attribution (``hop_seq`` /
``hop_bits`` / ``hop_layer`` / ``hop_stall``), all absent at the default
capacity 0.

The state stream (``SwarmConfig.trace_state_every``; DESIGN.md §12) is
simpler than either event stream because it is *epoch-indexed*: sample s
belongs to epoch ``s * every``, so slot ``epoch // every`` is written
exactly once, by exactly one epoch (non-sampled epochs target the
out-of-bounds slot S and are dropped by the scatter mode).  There is no
sequence counter, no overflow, and no ordering dependence — backend
bit-parity is free.  The per-node transmit-energy gauge reads the
simulator's own ``e_tx`` accumulator directly: energy accrues per sender
(``transfer.progress``) and is only summed to swarm level in summarize.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import SwarmConfig
from repro.obs.scopes import phase
from repro.trace import schema


def enabled(cfg: SwarmConfig) -> bool:
    return cfg.trace_capacity > 0


def init_trace(cfg: SwarmConfig, n: int) -> dict:
    """Trace-state entries for ``init_state`` — ``{}`` when tracing is off,
    so the untraced state pytree is unchanged field-for-field."""
    if not enabled(cfg):
        return {}
    Q = cfg.queue_slots
    return {
        "trace_records": schema.empty_buffer(cfg.trace_capacity),
        "trace_overflow": jnp.int32(0),
        "q_src": jnp.zeros((n, Q), jnp.int32),
        "q_energy": jnp.zeros((n, Q), jnp.float32),
        "q_txtime": jnp.zeros((n, Q), jnp.float32),
        "tx_src": jnp.zeros((n,), jnp.int32),
        "tx_energy": jnp.zeros((n,), jnp.float32),
        "tx_txtime": jnp.zeros((n,), jnp.float32),
    }


def hops_enabled(cfg: SwarmConfig) -> bool:
    return cfg.trace_hop_capacity > 0


def init_hops(cfg: SwarmConfig, n: int) -> dict:
    """Hop-stream state entries for ``init_state`` — ``{}`` when hop
    capture is off, so the state pytree is unchanged field-for-field."""
    if not hops_enabled(cfg):
        return {}
    return {
        "trace_hops": schema.empty_hop_buffer(cfg.trace_hop_capacity),
        "trace_hop_overflow": jnp.int32(0),
        "hop_counter": jnp.int32(0),
        # in-flight hop attribution, one slot per node (single outgoing
        # transfer per node, §3.2): the hop's seq, the bits staged at
        # initiate (tx_bits decrements in flight), the boundary layer the
        # task was snapped to, and the stall ticks accumulated so far
        "hop_seq": jnp.zeros((n,), jnp.int32),
        "hop_bits": jnp.zeros((n,), jnp.float32),
        "hop_layer": jnp.zeros((n,), jnp.int32),
        "hop_stall": jnp.zeros((n,), jnp.int32),
    }


def state_enabled(cfg: SwarmConfig) -> bool:
    return cfg.trace_state_every > 0


def num_state_samples(cfg: SwarmConfig) -> int:
    """Static slot count S = ceil(n_epochs / every) of the state buffers."""
    n_epochs = int(round(cfg.sim_time_s / cfg.decision_period_s))
    return (n_epochs + cfg.trace_state_every - 1) // cfg.trace_state_every


def state_nodes(cfg: SwarmConfig, n: int) -> int:
    """Recorded node-panel width M = min(N, trace_state_nodes or N)."""
    return min(n, cfg.trace_state_nodes or n)


def init_state_stream(cfg: SwarmConfig, n: int) -> dict:
    """State-stream entries for ``init_state`` — ``{}`` when off, so the
    untraced state pytree is unchanged field-for-field."""
    if not state_enabled(cfg):
        return {}
    S = num_state_samples(cfg)
    M = state_nodes(cfg, n)
    return {
        "trace_state": jnp.zeros((S, M, schema.NUM_STATE_GAUGES),
                                 jnp.float32),
        "trace_state_sys": jnp.zeros((S, schema.NUM_SYS_GAUGES),
                                     jnp.float32),
        # epoch index of each written slot; -1 marks never-written (only
        # possible if the scan ends before the slot's epoch)
        "trace_state_epochs": jnp.full((S,), -1.0, jnp.float32),
    }


def write_state(st, epoch_idx, t_end, cfg: SwarmConfig):
    """Snapshot node gauges + system aggregates at the end of an epoch.

    Called every epoch; epochs with ``epoch_idx % every != 0`` scatter to
    the out-of-bounds slot S and are dropped.  ``t_end`` is the simulation
    time at the end of the epoch.
    """
    S = st["trace_state"].shape[0]
    M = st["trace_state"].shape[1]
    every = cfg.trace_state_every
    sampled = (epoch_idx % every) == 0
    slot = jnp.where(sampled, epoch_idx // every, S)

    qdepth = jnp.sum(st["q_active"], axis=1).astype(jnp.float32)
    e_comp = st["proc_gflops"] * cfg.energy_per_gflop_j
    inflight_bits = jnp.where(st["tx_active"],
                              jnp.maximum(st["tx_bits"], 0.0), 0.0)
    node_rows = jnp.stack(
        [st["phi"][:M], qdepth[:M], e_comp[:M], st["e_tx"][:M],
         st["alive"][:M].astype(jnp.float32), inflight_bits[:M]], axis=-1)

    q = qdepth
    jain = (jnp.sum(q) ** 2) / (q.shape[0] * jnp.sum(q * q) + 1e-12)
    tx_act = jnp.sum(st["tx_active"].astype(jnp.float32))
    sys_row = jnp.stack(
        [t_end, jnp.sum(q) + tx_act, tx_act,
         st["done_count"].astype(jnp.float32),
         st["drop_count"].astype(jnp.float32),
         st["gen_count"].astype(jnp.float32),
         jnp.mean(q), jnp.max(q), jain,
         jnp.mean(st["phi"]), jnp.min(st["phi"]), jnp.max(st["phi"]),
         jnp.sum(st["e_comp"] + st["e_tx"])]).astype(jnp.float32)

    st = dict(st)
    # oob: drop is load-bearing — non-capture epochs target slot==capacity
    # on purpose, so the scatter is the stride filter itself (J003)
    st["trace_state"] = st["trace_state"].at[slot].set(
        node_rows, mode="drop")
    st["trace_state_sys"] = st["trace_state_sys"].at[slot].set(
        sys_row, mode="drop")
    # oob: same deliberate slot==capacity drop as above (J003)
    st["trace_state_epochs"] = st["trace_state_epochs"].at[slot].set(
        epoch_idx.astype(jnp.float32), mode="drop")
    return st


def _scatter_records(st, key_records, key_overflow, mask, seq, rows):
    """Shared scatter-by-seq + saturating-overflow core of both streams.

    Lanes with ``~mask`` (and captured-but-overflowed seqs) target slot
    ``capacity`` — out of bounds, dropped by the scatter mode — so the
    kept rows are deterministic regardless of lane order.
    """
    cap = st[key_records].shape[0]
    slot = jnp.where(mask, seq, cap)
    st = dict(st)
    # oob: drop is load-bearing — unmasked lanes and overflowed seqs
    # target slot==capacity so they vanish deterministically (J003)
    st[key_records] = st[key_records].at[slot].set(rows, mode="drop")
    # saturate at int32 max instead of wrapping (clamp the increment to
    # the remaining headroom — int32-only, no x64 dependence)
    inc = jnp.sum(mask & (seq >= cap)).astype(jnp.int32)
    room = jnp.int32(jnp.iinfo(jnp.int32).max) - st[key_overflow]
    st[key_overflow] = st[key_overflow] + jnp.minimum(inc, room)
    return st


def write_records(st, mask, *, seq, src, dst, created_t, completed_t,
                  exit_label, layers, hops, energy_j, tx_time_s):
    """Scatter one TaskRecord per ``mask`` lane into slot ``seq``."""
    with phase("trace_capture"):
        rows = schema.pack(seq, src, dst, created_t, completed_t, exit_label,
                           layers, hops, energy_j, tx_time_s)
        return _scatter_records(st, "trace_records", "trace_overflow", mask,
                                seq, rows)


def write_hop_records(st, mask, *, seq, src, dst, t_depart, t_arrive, bits,
                      boundary_layer, stall_ticks):
    """Scatter one HopRecord per ``mask`` lane into slot ``seq``."""
    with phase("trace_capture"):
        rows = schema.pack_hop(seq, src, dst, t_depart, t_arrive, bits,
                               boundary_layer, stall_ticks)
        return _scatter_records(st, "trace_hops", "trace_hop_overflow", mask,
                                seq, rows)


def traced_push(st, mask, cum, created, visited, *, src, energy, txtime,
                t_now, cfg: SwarmConfig, profile=None):
    """``queues.push`` plus attribution carry and drop records (and the
    task's profile id under a task mix).

    Tasks that find no free slot are dropped by ``push`` (counted in
    ``drop_count``); under tracing they additionally consume a seq — the
    record keyspace covers every task that ever *finished*, completed or
    not — and scatter a ``DROPPED`` record stamped at ``t_now``.
    """
    # deferred: queues ↔ trace
    from repro.swarm.queues import hop_count, push

    with phase("trace_capture"):
        n = st["q_active"].shape[0]
        has_free = ~jnp.all(st["q_active"], axis=1)
        dropped = mask & ~has_free
        extras = {"src": src, "energy": energy, "txtime": txtime}
        if profile is not None:
            extras["profile"] = profile
        st = push(st, mask, cum, created, visited, extras=extras)
        # seqs for the drops, after push consumed the accepted tasks' seqs
        # (i32-pinned reductions: numpy-style widening under x64 would drift
        # the seq-counter carry dtype — swarmlint J002)
        drop_seq = st["seq_counter"] + jnp.cumsum(
            dropped.astype(jnp.int32), dtype=jnp.int32) - 1
        st = dict(st)
        st["seq_counter"] = st["seq_counter"] + jnp.sum(
            dropped.astype(jnp.int32), dtype=jnp.int32)
        with phase("visited"):
            hops = hop_count(visited)
        return write_records(
            st, dropped, seq=drop_seq, src=src, dst=jnp.arange(n),
            created_t=created, completed_t=t_now,
            exit_label=jnp.int32(schema.DROPPED), layers=jnp.int32(0),
            hops=hops, energy_j=energy, tx_time_s=txtime)
