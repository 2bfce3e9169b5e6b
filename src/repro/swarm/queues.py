"""Struct-of-arrays task-queue ops (DESIGN.md §3.2).

Each node owns ``Q = cfg.queue_slots`` slots; a task is (active, cum_gflops,
created_t, seq, visited-set).  FIFO order is by global sequence number, so
``head_slot`` is an argmin over active seqs — all ops are fixed-shape
scatter/gathers that jit and vmap cleanly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.scopes import phase
from repro.swarm.tasks import TaskProfile

INT_MAX = jnp.iinfo(jnp.int32).max


def head_slot(st):
    """FIFO head per node: (head_slot_idx [N], has_task [N])."""
    seqv = jnp.where(st["q_active"], st["q_seq"], INT_MAX)
    head = jnp.argmin(seqv, axis=1)
    has = jnp.any(st["q_active"], axis=1)
    return head, has


def queued_gflops(st, profile: TaskProfile) -> jax.Array:
    """Remaining GFLOPs per node across all queued tasks (load metric T)."""
    rem = jnp.maximum(profile.total_gflops - st["q_cum"], 0.0)
    return jnp.sum(jnp.where(st["q_active"], rem, 0.0), axis=1)


def push(st, mask, cum, created, visited, extras=None):
    """Insert one task per node where mask; drops (with count) if full.

    ``extras`` scatters additional per-task columns into ``q_<name>``
    arrays alongside the core fields (the trace layer's attribution state,
    ``repro.trace.record``); ``None`` leaves the state untouched beyond
    the core fields — the untraced path is byte-for-byte the historical
    one.
    """
    with phase("queues"):
        n, Q = st["q_active"].shape
        free = jnp.argmin(st["q_active"], axis=1)          # first False slot
        has_free = ~jnp.all(st["q_active"], axis=1)
        ok = mask & has_free
        rows = jnp.arange(n)
        # dtype pins: integer cumsum/sum follow numpy and widen to i64 under
        # x64, which would drift the i32 seq fields' carry (swarmlint J002)
        seq = (st["seq_counter"]
               + jnp.cumsum(ok.astype(jnp.int32), dtype=jnp.int32) - 1)
        st = dict(st)
        for name, val in (extras or {}).items():
            k = f"q_{name}"
            # oob: `free` is an argmin over the slot axis, always in
            # [0, Q); drop mode is the .at[] default, never exercised (J003)
            st[k] = st[k].at[rows, free].set(
                jnp.where(ok, jnp.asarray(val, st[k].dtype),
                          st[k][rows, free]))
        # oob: same in-range `free` slot for every core-field scatter
        st["q_active"] = st["q_active"].at[rows, free].set(
            jnp.where(ok, True, st["q_active"][rows, free]))
        st["q_cum"] = st["q_cum"].at[rows, free].set(
            jnp.where(ok, cum, st["q_cum"][rows, free]))
        # oob: in-range `free` (argmin), see above
        st["q_created"] = st["q_created"].at[rows, free].set(
            jnp.where(ok, created, st["q_created"][rows, free]))
        # oob: in-range `free` (argmin), see above
        st["q_seq"] = st["q_seq"].at[rows, free].set(
            jnp.where(ok, seq, st["q_seq"][rows, free]))
        with phase("visited"):
            # oob: in-range `free` (argmin), see above
            st["q_visited"] = st["q_visited"].at[rows, free].set(
                jnp.where(ok[:, None], visited,
                          st["q_visited"][rows, free]))
        st["seq_counter"] = st["seq_counter"] + jnp.sum(
            ok.astype(jnp.int32), dtype=jnp.int32)
        # i32 count: exact under any reduction order, so the in-scan sum
        # cannot drift across executor backends (swarmlint J001, §8.2)
        st["drop_count"] = st["drop_count"] + jnp.sum(mask & ~has_free,
                                                      dtype=jnp.int32)
    return st


def pop_head(st, mask):
    """Deactivate the FIFO head where mask."""
    with phase("queues"):
        head, _ = head_slot(st)
        rows = jnp.arange(st["q_active"].shape[0])
        st = dict(st)
        # oob: `head` is an argmin over the slot axis, in [0, Q) (J003)
        st["q_active"] = st["q_active"].at[rows, head].set(
            jnp.where(mask, False, st["q_active"][rows, head]))
    return st
