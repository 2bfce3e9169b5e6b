"""Struct-of-arrays task-queue ops (DESIGN.md §3.2).

Each node owns ``Q = cfg.queue_slots`` slots; a task is (active, cum_gflops,
created_t, seq, visited-set), and under a task mix its profile id
(``q_profile``, written through ``push``'s ``extras``).  FIFO order is by
global sequence number, so ``head_slot`` is an argmin over active seqs, and
a push takes the first free slot, also an argmin.  The per-slot ``[n, Q]``
fields are addressed through a one-hot mask over the slot axis
(``slot_mask``): a write is an elementwise select and a read a masked
max-reduction, dense ops that fuse and update the loop carry in place,
where an indexed scatter or gather per node runs as a serial loop over the
nodes on the TPU.

A task's visited set is packed into ``W = ⌈n / 32⌉`` ``uint32`` words, node
``j`` at bit ``j % 32`` of word ``j // 32``: ``q_visited`` is
``[W, n, Q]``, one ``[n, Q]`` word plane per word, so each plane is read
and written through the same slot mask as the other fields.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.neighborhood import Neighborhood
from repro.obs.scopes import phase
from repro.swarm.tasks import ProfileMix, pick

INT_MAX = jnp.iinfo(jnp.int32).max
WORD = 32                     # nodes per packed visited-set word


def head_slot(st):
    """FIFO head per node: (head_slot_idx [N], has_task [N])."""
    seqv = jnp.where(st["q_active"], st["q_seq"], INT_MAX)
    head = jnp.argmin(seqv, axis=1)
    has = jnp.any(st["q_active"], axis=1)
    return head, has


def slot_mask(idx, Q: int) -> jax.Array:
    """One-hot ``[n, Q]`` mask: True at slot ``idx[i]`` of row ``i``."""
    return jnp.arange(Q, dtype=idx.dtype)[None, :] == idx[:, None]


def slot_read(x, mask) -> jax.Array:
    """``x[rows, idx]`` bit for bit, for ``mask = slot_mask(idx, Q)``.

    A max over the row with every other slot set to the max's identity, so
    the one selected value comes back unchanged (a float sum with zeros
    would turn -0.0 into +0.0).
    """
    if jnp.issubdtype(x.dtype, jnp.floating):
        ident = -jnp.inf
    else:
        ident = jnp.iinfo(x.dtype).min
    return jnp.max(jnp.where(mask, x, jnp.asarray(ident, x.dtype)), axis=1)


def _col(val, dtype):
    """``val`` ([n] or scalar) as a column that broadcasts over the slots."""
    val = jnp.asarray(val, dtype)
    return val[:, None] if val.ndim else val


def slot_write(x, mask, val) -> jax.Array:
    """``x`` with ``val[i]`` in the slots ``mask`` selects (≤ 1 per row)."""
    return jnp.where(mask, _col(val, x.dtype), x)


def slot_add(x, mask, val) -> jax.Array:
    """``x.at[rows, idx].add(val)`` bit for bit: only the masked slot adds,
    so an unmasked -0.0 stays -0.0."""
    return jnp.where(mask, x + _col(val, x.dtype), x)


def visited_words(n: int) -> int:
    """Words of one packed visited set over ``n`` nodes."""
    return -(-n // WORD)


def head_visited(q_visited, mask) -> jax.Array:
    """``uint32 [W, n]``: the visited words of the slots ``mask`` selects
    (one per row), read plane by plane like any slot field."""
    return jax.vmap(slot_read, in_axes=(0, None))(q_visited, mask)


def node_bits(ids, W: int) -> jax.Array:
    """``uint32 [W, ...]``: the one-node sets ``{ids}``, packed."""
    ids = jnp.asarray(ids, jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), (ids % WORD).astype(jnp.uint32))
    word = jnp.arange(W, dtype=jnp.int32).reshape((W,) + (1,) * ids.ndim)
    return jnp.where(word == ids // WORD, bit, jnp.uint32(0))


def unpack_visited(words, n: int) -> jax.Array:
    """``bool [..., n]``: the sets held by ``words`` ``[W, ...]``."""
    shift = jnp.arange(WORD, dtype=jnp.uint32).reshape(
        (WORD,) + (1,) * (words.ndim - 1))
    bits = (words[:, None] >> shift) & jnp.uint32(1)        # [W, 32, ...]
    bits = bits.reshape((-1,) + words.shape[1:])[:n]
    return jnp.moveaxis(bits, 0, -1).astype(bool)


def has_node(words, ids) -> jax.Array:
    """``bool [n, K]``: whether set ``i`` of ``words`` ``[W, n]`` holds
    node ``ids[i, k]`` (a bit test, no unpacking)."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0], dtype=jnp.int32)[:, None]
    w = words[ids // WORD, rows]
    return ((w >> (ids % WORD).astype(jnp.uint32)) & jnp.uint32(1)) == 1


def visited_neighbors(words, nbhd: Neighborhood) -> jax.Array:
    """``bool`` shaped like ``nbhd.mask``: whether set ``i`` of ``words``
    ``[W, n]`` holds the node in each neighbour slot — the sets unpacked
    over a dense neighbourhood, a bit test at the ids of neighbour lists
    (an ``[n, K]`` gather of words, never an ``[n, n]`` row)."""
    if nbhd.ids is None:
        return unpack_visited(words, nbhd.mask.shape[1])
    return has_node(words, nbhd.ids)


def hop_count(words) -> jax.Array:
    """``int32``: the size of each packed set (a popcount over the words)."""
    return jnp.sum(jax.lax.population_count(words), axis=0, dtype=jnp.int32)


def queued_gflops(st, profile) -> jax.Array:
    """Remaining GFLOPs per node across all queued tasks (load metric T),
    each task against its own profile's total under a ``ProfileMix``."""
    if isinstance(profile, ProfileMix):
        with phase("task_profile"):
            total = pick(profile.total_gflops, st["q_profile"])
    else:
        total = profile.total_gflops
    rem = jnp.maximum(total - st["q_cum"], 0.0)
    return jnp.sum(jnp.where(st["q_active"], rem, 0.0), axis=1)


def push(st, mask, cum, created, visited, extras=None):
    """Insert one task per node where mask; drops (with count) if full.

    ``visited`` holds each task's packed set, ``uint32 [W, n]``.
    ``extras`` writes additional per-task columns into ``q_<name>`` arrays
    alongside the core fields (the trace layer's attribution state,
    ``repro.trace.record``); ``None`` leaves the state untouched beyond
    the core fields — the untraced path is byte-for-byte the historical
    one.
    """
    with phase("queues"):
        Q = st["q_active"].shape[1]
        free = jnp.argmin(st["q_active"], axis=1)          # first False slot
        has_free = ~jnp.all(st["q_active"], axis=1)
        ok = mask & has_free
        # dtype pins: integer cumsum/sum follow numpy and widen to i64 under
        # x64, which would drift the i32 seq fields' carry (swarmlint J002)
        seq = (st["seq_counter"]
               + jnp.cumsum(ok.astype(jnp.int32), dtype=jnp.int32) - 1)
        put = slot_mask(free, Q) & ok[:, None]
        st = dict(st)
        for name, val in (extras or {}).items():
            st[f"q_{name}"] = slot_write(st[f"q_{name}"], put, val)
        st["q_active"] = st["q_active"] | put
        st["q_cum"] = slot_write(st["q_cum"], put, cum)
        st["q_created"] = slot_write(st["q_created"], put, created)
        st["q_seq"] = slot_write(st["q_seq"], put, seq)
        with phase("visited"):
            st["q_visited"] = jax.vmap(slot_write, in_axes=(0, None, 0))(
                st["q_visited"], put, visited)
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
        pop = slot_mask(head, st["q_active"].shape[1]) & mask[:, None]
        st = dict(st)
        st["q_active"] = st["q_active"] & ~pop
    return st
