"""Packed per-task visited sets against the bool bitmap they replaced.

A task's visited set is kept as ``W = ⌈N / 32⌉`` ``uint32`` words, node
``j`` at bit ``j % 32`` of word ``j // 32``: ``q_visited`` ``[W, N, Q]``,
``tx_visited`` ``[W, N]``.  The plain reference here is the bool
formulation: ``q_visited`` ``[N, Q, N]`` written by an indexed row scatter
and read by row gathers.  ``pack``/``unpack`` convert between the two.
Every touch of the sets (``push`` for arrivals and deliveries, with and
without trace extras, ``initiate``'s carry, ``progress``'s origin mark,
the dense and sparse RandomAcyclic masks, the popcount hop counts) must
give the bool reference's bits, on full queues, empty queues, empty and
full sets and under ``vmap``; and a whole sparse run at N = 64 (W = 2)
must give the outputs the bool bitmap gave.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core.neighborhood import Neighborhood
from repro.fleet import run_batch
from repro.swarm import queues, transfer
from repro.swarm.queues import (has_node, head_slot, head_visited,
                                hop_count, node_bits, slot_mask,
                                unpack_visited, visited_words)
from repro.swarm.simulator import (BIG, RANDOM_ACYCLIC, _strategy_decision,
                                   init_state)
from repro.swarm.tasks import make_profile
from repro.trace import record as trace_record
from repro.trace import schema

Q = 5
NS = (1, 30, 31, 32, 33, 64, 100)      # W = 1 to 4, bit 31 included
STATE_NS = (30, 64)                     # W = 1 and W = 2


# ---------------------------------------------------------------------------
# the bool reference
# ---------------------------------------------------------------------------


@jax.jit
def pack(bits):
    """``uint32 [W, ...]``: the bool sets ``bits`` ``[..., n]`` as words."""
    bits = jnp.asarray(bits, bool)
    n = bits.shape[-1]
    W = -(-n // 32)
    bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, 32 * W - n)])
    b = bits.reshape(bits.shape[:-1] + (W, 32)).astype(jnp.uint32)
    words = jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jnp.moveaxis(words, -1, 0)


@jax.jit(static_argnums=1)
def unpack(words, n):
    """``bool [..., n]``: the sets held by ``words`` ``[W, ...]``."""
    w = jnp.moveaxis(jnp.asarray(words, jnp.uint32), 0, -1)     # [..., W]
    b = (w[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return b.reshape(w.shape[:-1] + (-1,))[..., :n].astype(bool)


def _sets(rng, shape, fill):
    """Bool sets: ``random``, ``none`` (all empty) or ``all`` (all full)."""
    if fill == "none":
        return np.zeros(shape, bool)
    if fill == "all":
        return np.ones(shape, bool)
    return rng.random(shape) < 0.3


def _cfg(n, traced=False):
    cfg = dataclasses.replace(SwarmConfig(), num_workers=n, queue_slots=Q)
    if traced:
        cfg = dataclasses.replace(cfg, trace_capacity=4 * n * Q,
                                  trace_hop_capacity=4 * n * Q)
    return cfg


def _state(n, seed, fill="random", sets="random", traced=False):
    """A state with random queues and visited sets, and its bool sets."""
    cfg = _cfg(n, traced)
    rng = np.random.default_rng(seed)
    st = dict(init_state(jax.random.PRNGKey(seed), cfg, n))
    if fill == "random":
        active = rng.random((n, Q)) < 0.5
        active[0], active[-1] = True, False
    else:
        active = np.full((n, Q), fill == "full")
    st["q_active"] = jnp.asarray(active)
    st["q_seq"] = jnp.asarray(rng.permutation(n * Q).reshape(n, Q),
                              jnp.int32)
    st["seq_counter"] = jnp.int32(n * Q)
    q_bool = _sets(rng, (n, Q, n), sets)
    tx_bool = _sets(rng, (n, n), sets)
    st["q_visited"] = pack(q_bool)
    st["tx_visited"] = pack(tx_bool)
    return st, cfg, rng, q_bool, tx_bool


def _mask(rng, mode, n):
    if mode == "none":
        return np.zeros(n, bool)
    if mode == "all":
        return np.ones(n, bool)
    return rng.random(n) < 0.5


def ref_push(q_active, q_bool, mask, vis_bool):
    """The bool row write: the first free slot takes the pushed set."""
    n = q_active.shape[0]
    rows = np.arange(n)
    free = np.argmin(q_active, axis=1)
    ok = mask & ~np.all(q_active, axis=1)
    q_bool = q_bool.copy()
    q_bool[rows[ok], free[ok]] = vis_bool[ok]
    return q_bool


def assert_words(got, want_bool):
    """Packed ``got`` holds exactly the bool sets ``want_bool``."""
    got = np.asarray(got)
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got, np.asarray(pack(want_bool)))
    np.testing.assert_array_equal(np.asarray(unpack(got, want_bool.shape[-1])),
                                  want_bool)


# ---------------------------------------------------------------------------
# the layout and the helpers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", NS)
def test_pack_unpack_round_trip(n):
    rng = np.random.default_rng(n)
    W = visited_words(n)
    assert W == -(-n // 32)
    x = rng.random((n, Q, n)) < 0.5
    words = pack(x)
    assert words.shape == (W, n, Q) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_visited(words, n)), x)
    np.testing.assert_array_equal(np.asarray(unpack(words, n)), x)
    # node j is bit j % 32 of word j // 32, and the padding bits stay 0
    want = np.zeros((W, n), np.uint32)
    for j in range(n):
        want[j // 32, j] = np.uint32(1 << (j % 32))
    np.testing.assert_array_equal(np.asarray(pack(np.eye(n, dtype=bool))),
                                  want)
    np.testing.assert_array_equal(
        np.asarray(node_bits(jnp.arange(n, dtype=jnp.int32), W)), want)
    np.testing.assert_array_equal(np.asarray(pack(unpack_visited(words, n))),
                                  np.asarray(words))


@pytest.mark.parametrize("sets", ["random", "none", "all"])
@pytest.mark.parametrize("n", NS)
def test_head_reads_match_bool(n, sets):
    """The head task's words, their unpacked [N, N] mask (dense decision),
    the bit test at K neighbour ids (sparse decision) and the popcount
    hop count, each against the bool row gather."""
    st, _, rng, q_bool, _ = _state(n, n + 1, sets=sets)
    rows = np.arange(n)
    head = np.asarray(head_slot(st)[0])
    row_bool = q_bool[rows, head]                                # [n, n]
    nbr = rng.integers(0, n, (n, min(n, 16))).astype(np.int32)
    ids = rng.integers(0, n, (n,)).astype(np.int32)

    @jax.jit
    def reads(q_visited, head, nbr, ids):
        words = head_visited(q_visited, slot_mask(head, Q))
        return (words, unpack_visited(words, n), has_node(words, nbr),
                hop_count(words), node_bits(ids, visited_words(n)))

    words, dense, sparse, hops, bits = reads(st["q_visited"], head, nbr, ids)
    assert_words(words, row_bool)
    np.testing.assert_array_equal(np.asarray(dense), row_bool)
    np.testing.assert_array_equal(np.asarray(sparse),
                                  row_bool[rows[:, None], nbr])
    assert hops.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(hops), row_bool.sum(-1))
    np.testing.assert_array_equal(
        np.asarray(bits), np.asarray(pack(np.eye(n, dtype=bool)[ids])))


# ---------------------------------------------------------------------------
# the writes: push, initiate's carry, progress's origin mark
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("source", ["arrival", "delivery"])
@pytest.mark.parametrize("mode", ["random", "none", "all"])
@pytest.mark.parametrize("fill", ["random", "full", "empty"])
@pytest.mark.parametrize("n", STATE_NS)
def test_push_matches_bool(n, fill, mode, source, traced):
    st, cfg, rng, q_bool, _ = _state(n, 3, fill, traced=traced)
    mask = _mask(rng, mode, n)
    # an arrival carries the empty set, a delivery its visited set
    vis_bool = (np.zeros((n, n), bool) if source == "arrival"
                else rng.random((n, n)) < 0.5)
    vis = pack(vis_bool)
    vec = jnp.zeros((n,), jnp.float32)
    if traced:
        got = trace_record.traced_push(
            st, jnp.asarray(mask), vec, vec, vis, src=jnp.arange(n),
            energy=0.0, txtime=0.0, t_now=jnp.float32(1.0), cfg=cfg)
        # a dropped task's record counts its set (popcount hops)
        rec = np.asarray(got["trace_records"])
        dropped = rec[rec[:, schema.EXIT_LABEL] == schema.DROPPED]
        assert len(dropped) == int(
            (mask & np.all(np.asarray(st["q_active"]), axis=1)).sum())
        np.testing.assert_array_equal(
            dropped[:, schema.HOPS],
            vis_bool.sum(-1)[dropped[:, schema.SRC].astype(int)])
    else:
        got = queues.push(st, jnp.asarray(mask), vec, vec, vis)
    assert_words(got["q_visited"],
                 ref_push(np.asarray(st["q_active"]), q_bool, mask, vis_bool))


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("mode", ["random", "none", "all"])
@pytest.mark.parametrize("fill", ["random", "full", "empty"])
@pytest.mark.parametrize("n", STATE_NS)
def test_initiate_matches_bool(n, fill, mode, traced):
    st, cfg, rng, q_bool, tx_bool = _state(n, 5, fill, traced=traced)
    elig = _mask(rng, mode, n) & np.any(np.asarray(st["q_active"]), axis=1)
    got = transfer.initiate(st, jnp.asarray(elig),
                            jnp.zeros((n,), jnp.int32), jnp.float32(0.0),
                            make_profile(cfg))
    head = np.asarray(head_slot(st)[0])
    want = np.where(elig[:, None], q_bool[np.arange(n), head], tx_bool)
    assert_words(got["tx_visited"], want)


def ref_deliveries(tx_active, tx_bits, tx_dst):
    """``{destination: origin}`` of one tick's deliveries: the lowest
    arrived origin wins each destination."""
    won = {}
    for i in np.flatnonzero(tx_active & (tx_bits <= 0.0)):
        won.setdefault(int(tx_dst[i]), int(i))
    return won


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("fill", ["random", "full", "empty"])
@pytest.mark.parametrize("n", STATE_NS)
def test_progress_marks_origin(n, fill, traced):
    st, cfg, rng, q_bool, tx_bool = _state(n, 7, fill, traced=traced)
    tx_active = rng.random(n) < 0.7
    tx_bits = np.where(rng.random(n) < 0.7, 0.0, 1e9).astype(np.float32)
    tx_dst = rng.integers(0, n, n).astype(np.int32)
    st.update(tx_active=jnp.asarray(tx_active), tx_bits=jnp.asarray(tx_bits),
              tx_dst=jnp.asarray(tx_dst))
    rate = jnp.zeros((n,), jnp.float32)        # nothing in flight moves
    got = transfer.progress(st, rate, jnp.ones((n,), bool), cfg,
                            jnp.float32(1.0))
    won = ref_deliveries(tx_active, tx_bits, tx_dst)
    assert won
    mask = np.zeros(n, bool)
    vis_bool = np.zeros((n, n), bool)
    for dst, origin in won.items():
        mask[dst] = True
        vis_bool[dst] = tx_bool[origin]
        vis_bool[dst, origin] = True                  # the origin's mark
    assert_words(got["q_visited"],
                 ref_push(np.asarray(st["q_active"]), q_bool, mask, vis_bool))


# ---------------------------------------------------------------------------
# the reads: RandomAcyclic's unvisited-neighbour masks
# ---------------------------------------------------------------------------


def ref_acyclic(key, adj, row_bool, ids, p):
    """RandomAcyclic's draw over the bool sets (``ids`` maps the columns
    of ``adj`` to nodes: ``arange`` dense, the neighbour lists sparse)."""
    n = adj.shape[0]
    rows = np.arange(n)
    _, _, k3 = jax.random.split(key, 3)
    amask = adj & ~row_bool[rows[:, None], ids]
    score = jnp.where(amask, jax.random.gumbel(k3, adj.shape), -BIG)
    tgt = ids[rows, np.asarray(jnp.argmax(score, axis=1))]
    do = np.asarray(jax.random.bernoulli(jax.random.fold_in(k3, 1), p,
                                         (n,))) & amask.any(1)
    return do, tgt


@pytest.mark.parametrize("sets", ["random", "none", "all"])
@pytest.mark.parametrize("path", ["dense", "sparse"])
@pytest.mark.parametrize("n", STATE_NS)
def test_acyclic_decision_matches_bool(n, path, sets):
    st, cfg, rng, q_bool, _ = _state(n, 11, sets=sets)
    cfg = dataclasses.replace(cfg, random_acyclic_p=1.0)
    key = jax.random.PRNGKey(n)
    T = jnp.asarray(rng.uniform(0, 50, n), jnp.float32)
    row_bool = q_bool[np.arange(n), np.asarray(head_slot(st)[0])]
    if path == "dense":
        adj = rng.random((n, n)) < 0.4
        np.fill_diagonal(adj, False)
        ids = np.broadcast_to(np.arange(n), (n, n))
        nbhd = Neighborhood(jnp.asarray(adj))
    else:
        K = 8
        ids = np.sort(np.stack([rng.choice(np.delete(np.arange(n), i), K,
                                           replace=False)
                                for i in range(n)]), axis=1)
        adj = rng.random((n, K)) < 0.6
        nbhd = Neighborhood(jnp.asarray(adj), jnp.asarray(ids, jnp.int32))
    do, tgt, _ = _strategy_decision(
        st, jnp.int32(RANDOM_ACYCLIC), nbhd,
        jnp.ones(adj.shape, jnp.float32), T, key, cfg)
    want_do, want_tgt = ref_acyclic(key, adj, row_bool, ids, 1.0)
    np.testing.assert_array_equal(np.asarray(do), want_do)
    np.testing.assert_array_equal(np.asarray(tgt)[want_do],
                                  want_tgt[want_do])
    if sets == "all":
        assert not np.asarray(do).any()        # every neighbour visited


# ---------------------------------------------------------------------------
# under vmap, and the structure of push
# ---------------------------------------------------------------------------


def test_vmap_over_runs_matches_bool():
    """Push, initiate and progress over a batch of runs, as the executor
    vmaps them, at N = 33 (W = 2)."""
    n = 33
    runs = [_state(n, seed, fill, traced=True)
            for seed, fill in ((0, "random"), (1, "full"), (2, "empty"))]
    cfg = runs[0][1]
    profile = make_profile(cfg)
    rng = np.random.default_rng(13)
    batch = jax.tree.map(lambda *xs: jnp.stack(xs), *[r[0] for r in runs])
    mask = rng.random((3, n)) < 0.5
    vis_bool = rng.random((3, n, n)) < 0.5
    vis = jax.vmap(pack)(vis_bool)
    vec = jnp.zeros((n,), jnp.float32)
    rate = jnp.zeros((n,), jnp.float32)

    def one(st, m, v):
        st = queues.push(st, m, vec, vec, v)
        mid = st["q_visited"]
        st = transfer.initiate(st, m & jnp.any(st["q_active"], axis=1),
                               jnp.zeros((n,), jnp.int32), jnp.float32(0.0),
                               profile)
        return mid, transfer.progress(st, rate, jnp.ones((n,), bool), cfg,
                                      jnp.float32(1.0))

    mid, got = jax.jit(jax.vmap(one))(batch, jnp.asarray(mask), vis)
    for r, (st, _, _, q_bool, tx_bool) in enumerate(runs):
        want = ref_push(np.asarray(st["q_active"]), q_bool, mask[r],
                        vis_bool[r])
        assert_words(mid[r], want)
        one_st = jax.tree.map(lambda x: x[r], batch)
        single = one(one_st, jnp.asarray(mask[r]), vis[r])[1]
        for k in ("q_visited", "tx_visited"):
            np.testing.assert_array_equal(np.asarray(got[k][r]),
                                          np.asarray(single[k]))


def indexed_operand_shapes(fn, *args):
    """Operand shapes of every gather and scatter in ``fn``'s jaxpr."""
    from repro.analysis.jaxpr.jaxpr_util import iter_eqns
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [tuple(site.eqn.invars[0].aval.shape)
            for site in iter_eqns(jaxpr.jaxpr)
            if site.eqn.primitive.name.startswith(("gather", "scatter"))]


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("n", STATE_NS)
def test_push_has_no_indexed_access_to_the_words(n, traced):
    st, cfg, rng, _, _ = _state(n, 17, traced=traced)
    mask = jnp.asarray(_mask(rng, "random", n))
    vec = jnp.zeros((n,), jnp.float32)
    vis = jnp.zeros_like(st["tx_visited"])
    if traced:
        fn = (lambda s: trace_record.traced_push(
            s, mask, vec, vec, vis, src=jnp.arange(n), energy=0.0,
            txtime=0.0, t_now=jnp.float32(1.0), cfg=cfg))
    else:
        fn = (lambda s: queues.push(s, mask, vec, vec, vis))
    shapes = indexed_operand_shapes(fn, st)
    assert st["q_visited"].shape not in shapes
    assert st["tx_visited"].shape not in shapes
    if not traced:
        assert shapes == []


# ---------------------------------------------------------------------------
# a whole run: sparse, N = 64 (W = 2), hop capture on
# ---------------------------------------------------------------------------

# Outputs of the bool-bitmap simulator for this configuration (jax 0.9.0):
# a digest of the summarize statistics, of the task records and of the hop
# records, and the most hops a task took.  Slow nodes and eager offloading
# make tasks hop up to four times, so RandomAcyclic's draws read the sets.
WHOLE_RUN_CFG = dataclasses.replace(
    SwarmConfig(), num_workers=64, sim_time_s=6.0, capability_mean=80.0,
    neighbor_mode="sparse", neighbor_k=16, random_offload_p=0.5,
    random_acyclic_p=0.5, trace_capacity=8192, trace_hop_capacity=8192)
_WHOLE_RUN_PIN = {
    0: ('9cd35a64ba7e3616', '0469267285468c6e', '291b448e372059bf', 0),
    1: ('d6027a5ca6bd9a99', '9e8a3bd60386c19c', 'ab0e8f87ed4e7ad2', 3),
    2: ('f308ef65e7e5f2fb', '54224dd681b293b4', 'b953326c45699cac', 3),
    3: ('9fea7ec87a3feae9', '44e75f85ea6a1415', '6de0b42c11e33b45', 2),
    4: ('3e12992e2251a898', 'f7a9b7018ddf3d1b', 'c1f57f192522ce69', 4),
}


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("strategy", sorted(_WHOLE_RUN_PIN))
def test_sparse_run_matches_bool_era(strategy):
    cfg = WHOLE_RUN_CFG
    assert visited_words(cfg.num_workers) == 2
    out = run_batch(jax.random.PRNGKey(2024), cfg, jnp.int32(strategy),
                    cfg.num_workers, 2)
    rec = np.asarray(out["trace_records"])
    got = (_digest(out[k] for k in sorted(out) if not k.startswith("trace_")),
           _digest([out["trace_records"]]), _digest([out["trace_hops"]]),
           int(rec[rec[..., schema.SEQ] >= 0][:, schema.HOPS].max()))
    assert got == _WHOLE_RUN_PIN[strategy]
