"""One-hot slot addressing of the task queues against indexed scatters.

``swarm/queues.py`` reads and writes the per-slot ``[n, Q]`` fields through
a one-hot mask over the slot axis.  The plain reference here is the indexed
formulation it replaced: ``x[rows, idx]`` gathers and ``x.at[rows, idx]``
scatters.  Both must agree bit for bit on every field of the state, for
``push`` (with and without trace extras), ``pop_head``, the compute pass and
``transfer.initiate``, on full queues, empty queues, all-false masks and
under ``vmap``.  The reference keeps the visited sets as the bool
``[n, Q, n]`` bitmap, unpacking and packing the state's words around it
(``tests/test_visited_bits.py``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_visited_bits import indexed_operand_shapes, pack, unpack

from repro.configs.base import SwarmConfig
from repro.swarm import queues, transfer
from repro.swarm.queues import head_slot
from repro.swarm.simulator import _compute_pass, init_state
from repro.swarm.tasks import (boundary_bits, layer_of, make_profile,
                               snap_to_boundary)
from repro.trace import record as trace_record

N, Q = 6, 5
SLOT_FIELDS = ("q_active", "q_cum", "q_created", "q_seq")


# ---------------------------------------------------------------------------
# the indexed reference
# ---------------------------------------------------------------------------


def ref_push(st, mask, cum, created, visited, extras=None):
    n = st["q_active"].shape[0]
    free = jnp.argmin(st["q_active"], axis=1)
    has_free = ~jnp.all(st["q_active"], axis=1)
    ok = mask & has_free
    rows = jnp.arange(n)
    seq = (st["seq_counter"]
           + jnp.cumsum(ok.astype(jnp.int32), dtype=jnp.int32) - 1)
    st = dict(st)
    for name, val in (extras or {}).items():
        k = f"q_{name}"
        st[k] = st[k].at[rows, free].set(
            jnp.where(ok, jnp.asarray(val, st[k].dtype), st[k][rows, free]))
    for k, val in (("q_active", True), ("q_cum", cum),
                   ("q_created", created), ("q_seq", seq)):
        st[k] = st[k].at[rows, free].set(
            jnp.where(ok, val, st[k][rows, free]))
    q_bool = unpack(st["q_visited"], n)
    st["q_visited"] = pack(q_bool.at[rows, free].set(
        jnp.where(ok[:, None], unpack(visited, n), q_bool[rows, free])))
    st["seq_counter"] = st["seq_counter"] + jnp.sum(
        ok.astype(jnp.int32), dtype=jnp.int32)
    st["drop_count"] = st["drop_count"] + jnp.sum(mask & ~has_free,
                                                  dtype=jnp.int32)
    return st


def ref_pop_head(st, mask):
    head, _ = head_slot(st)
    rows = jnp.arange(st["q_active"].shape[0])
    st = dict(st)
    st["q_active"] = st["q_active"].at[rows, head].set(
        jnp.where(mask, False, st["q_active"][rows, head]))
    return st


def ref_compute_pass(st, budget, targets_cum, t_now, cfg):
    from repro.core.early_exit import exit_accuracy
    eJ = cfg.energy_per_gflop_j
    n = st["q_active"].shape[0]
    rows = jnp.arange(n)
    head, has = head_slot(st)
    cur = st["q_cum"][rows, head]
    rem = jnp.maximum(targets_cum - cur, 0.0)
    adv = jnp.where(has, jnp.minimum(budget, rem), 0.0)
    new_cum = cur + adv
    completed = has & (new_cum >= targets_cum - 1e-6)
    lat = t_now - st["q_created"][rows, head]
    acc = exit_accuracy(st["xi_label"], cfg.exit_accuracy)
    st = dict(st)
    st["q_cum"] = st["q_cum"].at[rows, head].set(
        jnp.where(has, new_cum, st["q_cum"][rows, head]))
    st["proc_gflops"] = st["proc_gflops"] + adv
    st["e_comp"] = st["e_comp"] + adv * eJ
    st["done_count"] = st["done_count"] + jnp.sum(completed,
                                                  dtype=jnp.int32)
    st["lat_sum"] = st["lat_sum"] + jnp.sum(jnp.where(completed, lat, 0.0))
    st["acc_sum"] = st["acc_sum"] + jnp.sum(jnp.where(completed, acc, 0.0))
    st["q_active"] = st["q_active"].at[rows, head].set(
        jnp.where(completed, False, st["q_active"][rows, head]))
    if trace_record.enabled(cfg):
        st["q_energy"] = st["q_energy"].at[rows, head].add(adv * eJ)
        hops = jnp.sum(unpack(st["q_visited"], n)[rows, head], axis=-1)
        st = trace_record.write_records(
            st, completed, seq=st["q_seq"][rows, head],
            src=st["q_src"][rows, head], dst=rows,
            created_t=st["q_created"][rows, head], completed_t=t_now,
            exit_label=st["xi_label"], layers=st["xi_layers"],
            hops=hops, energy_j=st["q_energy"][rows, head],
            tx_time_s=st["q_txtime"][rows, head])
    return st, budget - adv


def ref_initiate(st, elig, tgt, t0, profile):
    n = st["F"].shape[0]
    rows = jnp.arange(n)
    head, _ = head_slot(st)
    cum_h = st["q_cum"][rows, head]
    cum_snap = snap_to_boundary(profile, cum_h)
    bits = boundary_bits(profile, cum_h)
    st = dict(st)
    if "tx_src" in st:
        for f in ("src", "energy", "txtime"):
            st[f"tx_{f}"] = jnp.where(elig, st[f"q_{f}"][rows, head],
                                      st[f"tx_{f}"])
    if "hop_seq" in st:
        hseq = st["hop_counter"] + jnp.cumsum(
            elig.astype(jnp.int32), dtype=jnp.int32) - 1
        st["hop_seq"] = jnp.where(elig, hseq, st["hop_seq"])
        st["hop_counter"] = st["hop_counter"] + jnp.sum(
            elig.astype(jnp.int32), dtype=jnp.int32)
        st["hop_bits"] = jnp.where(elig, bits, st["hop_bits"])
        st["hop_layer"] = jnp.where(
            elig, jnp.clip(layer_of(profile, cum_h), 0,
                           profile.cum_gflops.shape[0] - 1),
            st["hop_layer"])
        st["hop_stall"] = jnp.where(elig, 0, st["hop_stall"])
    st["tx_dst"] = jnp.where(elig, tgt, st["tx_dst"])
    st["tx_bits"] = jnp.where(elig, bits, st["tx_bits"])
    st["tx_cum"] = jnp.where(elig, cum_snap, st["tx_cum"])
    st["tx_created"] = jnp.where(elig, st["q_created"][rows, head],
                                 st["tx_created"])
    st["tx_visited"] = pack(jnp.where(elig[:, None],
                                      unpack(st["q_visited"], n)[rows, head],
                                      unpack(st["tx_visited"], n)))
    st["tx_start"] = jnp.where(elig, t0, st["tx_start"])
    st["tx_count"] = st["tx_count"] + jnp.sum(elig, dtype=jnp.int32)
    st["tx_active"] = st["tx_active"] | elig
    return ref_pop_head(st, elig)


# ---------------------------------------------------------------------------
# seeded random states
# ---------------------------------------------------------------------------


def _cfg(traced: bool) -> SwarmConfig:
    cfg = dataclasses.replace(SwarmConfig(), num_workers=N, queue_slots=Q)
    if traced:
        cfg = dataclasses.replace(cfg, trace_capacity=64,
                                  trace_hop_capacity=64)
    return cfg


def _state(seed: int, fill: str, traced: bool):
    """A state whose queue fields are random; ``fill`` picks the occupancy:
    ``random`` (rows 0 and 1 forced full and empty), ``full`` or ``empty``."""
    cfg = _cfg(traced)
    rng = np.random.default_rng(seed)
    st = dict(init_state(jax.random.PRNGKey(seed), cfg, N))
    if fill == "random":
        active = rng.random((N, Q)) < 0.5
        active[0], active[1] = True, False
    else:
        active = np.full((N, Q), fill == "full")
    cum = rng.uniform(0.0, 14.0, (N, Q)).astype(np.float32)
    cum[rng.random((N, Q)) < 0.2] = -0.0          # signed zeros survive
    st["q_active"] = jnp.asarray(active)
    st["q_cum"] = jnp.asarray(cum)
    st["q_created"] = jnp.asarray(rng.uniform(0, 9, (N, Q)), jnp.float32)
    # distinct seqs, so the FIFO head is unique
    st["q_seq"] = jnp.asarray(rng.permutation(N * Q).reshape(N, Q),
                              jnp.int32)
    st["seq_counter"] = jnp.int32(N * Q)
    st["q_visited"] = pack(rng.random((N, Q, N)) < 0.3)
    st["xi_label"] = jnp.asarray(rng.integers(0, 3, N), jnp.int32)
    st["xi_layers"] = jnp.asarray(rng.choice(cfg.exit_points, N), jnp.int32)
    st["tx_active"] = jnp.asarray(rng.random(N) < 0.3)
    if traced:
        st["q_src"] = jnp.asarray(rng.integers(0, N, (N, Q)), jnp.int32)
        energy = rng.uniform(0, 3, (N, Q)).astype(np.float32)
        energy[rng.random((N, Q)) < 0.2] = -0.0
        st["q_energy"] = jnp.asarray(energy)
        st["q_txtime"] = jnp.asarray(rng.uniform(0, 2, (N, Q)), jnp.float32)
    return st, cfg, rng


def _mask(rng, mode: str, shape=(N,)):
    if mode == "none":
        return jnp.zeros(shape, bool)
    if mode == "all":
        return jnp.ones(shape, bool)
    return jnp.asarray(rng.random(shape) < 0.5)


def _bits(x):
    x = np.atleast_1d(np.asarray(x))
    return x.view(np.uint8) if x.dtype.kind == "f" else x


def assert_same(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.asarray(g).dtype == np.asarray(w).dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


CASES = [(seed, fill, mode) for seed in (0, 1)
         for fill in ("random", "full", "empty")
         for mode in ("random", "none", "all")]


# ---------------------------------------------------------------------------
# equality with the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("seed,fill,mode", CASES)
def test_push_matches_indexed(seed, fill, mode, traced):
    st, cfg, rng = _state(seed, fill, traced)
    mask = _mask(rng, mode)
    cum = jnp.asarray(rng.uniform(0, 5, N), jnp.float32)
    created = jnp.asarray(rng.uniform(0, 9, N), jnp.float32)
    visited = pack(rng.random((N, N)) < 0.5)
    extras = None
    if traced:   # an [n] column, a scalar and an int column cast to i32
        extras = {"src": jnp.arange(N), "energy": 0.0,
                  "txtime": jnp.asarray(rng.uniform(0, 1, N), jnp.float32)}
    got = queues.push(st, mask, cum, created, visited, extras=extras)
    want = ref_push(st, mask, cum, created, visited, extras=extras)
    assert_same(got, want)
    if fill == "full":      # nothing fits: every masked task is dropped
        assert int(got["drop_count"] - st["drop_count"]) == int(mask.sum())
        assert_same({k: got[k] for k in SLOT_FIELDS},
                    {k: st[k] for k in SLOT_FIELDS})


@pytest.mark.parametrize("seed,fill,mode", CASES)
def test_pop_head_matches_indexed(seed, fill, mode):
    st, _, rng = _state(seed, fill, False)
    mask = _mask(rng, mode)
    got = queues.pop_head(st, mask)
    assert_same(got, ref_pop_head(st, mask))
    if fill == "empty" or mode == "none":
        assert_same(got["q_active"], st["q_active"])


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("seed,fill,mode", CASES)
def test_compute_pass_matches_indexed(seed, fill, mode, traced):
    st, cfg, rng = _state(seed, fill, traced)
    profile = make_profile(cfg)
    targets = profile.cum_gflops[jnp.clip(st["xi_layers"], 0,
                                          profile.gflops.shape[0])]
    # "none" gives no budget: nothing advances or completes
    budget = _mask(rng, mode).astype(jnp.float32) * jnp.asarray(
        rng.uniform(0, 12, N), jnp.float32)
    t_now = jnp.float32(10.0)
    got = _compute_pass(st, budget, targets, t_now, cfg)
    want = ref_compute_pass(st, budget, targets, t_now, cfg)
    assert_same(got, want)


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("seed,fill,mode", CASES)
def test_initiate_matches_indexed(seed, fill, mode, traced):
    st, cfg, rng = _state(seed, fill, traced)
    profile = make_profile(cfg)
    elig = _mask(rng, mode) & jnp.any(st["q_active"], axis=1)
    tgt = jnp.asarray(rng.integers(0, N, N), jnp.int32)
    got = transfer.initiate(st, elig, tgt, jnp.float32(2.0), profile)
    want = ref_initiate(st, elig, tgt, jnp.float32(2.0), profile)
    assert_same(got, want)


@pytest.mark.parametrize("fill", ["random", "full", "empty"])
def test_vmap_over_runs_matches_indexed(fill):
    """A batch of runs with different states, as the executor vmaps them."""
    runs = [_state(seed, fill, True) for seed in range(3)]
    cfg = runs[0][1]
    profile = make_profile(cfg)
    batch = jax.tree.map(lambda *xs: jnp.stack(xs), *[r[0] for r in runs])
    rng = np.random.default_rng(7)
    mask = _mask(rng, "random", (3, N))
    cum = jnp.asarray(rng.uniform(0, 5, (3, N)), jnp.float32)
    visited = jax.vmap(pack)(rng.random((3, N, N)) < 0.5)
    budget = jnp.asarray(rng.uniform(0, 12, (3, N)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, N, (3, N)), jnp.int32)

    def step(push_fn, pop_fn, pass_fn, init_fn):
        def one(st, m, c, v, b, g):
            extras = {"src": jnp.arange(N), "energy": 0.0, "txtime": 0.0}
            st = push_fn(st, m, c, c, v, extras=extras)
            targets = profile.cum_gflops[jnp.clip(
                st["xi_layers"], 0, profile.gflops.shape[0])]
            st, _ = pass_fn(st, b, targets, jnp.float32(3.0), cfg)
            st = init_fn(st, m & jnp.any(st["q_active"], axis=1), g,
                         jnp.float32(3.0), profile)
            return pop_fn(st, ~m)
        return jax.jit(jax.vmap(one))(batch, mask, cum, visited, budget, tgt)

    got = step(queues.push, queues.pop_head, _compute_pass,
               transfer.initiate)
    want = step(ref_push, ref_pop_head, ref_compute_pass, ref_initiate)
    assert_same(got, want)


# ---------------------------------------------------------------------------
# the helpers on their own
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_slot_read_is_the_indexed_gather(dtype):
    rng = np.random.default_rng(3)
    if dtype == jnp.int32:
        x = rng.integers(np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                         (N, Q))
        x[0, :] = np.iinfo(np.int32).min     # the identity itself
    else:
        x = rng.standard_normal((N, Q))
        x[0, :] = [-0.0, 0.0, -np.inf, np.inf, -0.0]
    x = jnp.asarray(x, dtype)
    for idx in (np.zeros(N, int), rng.integers(0, Q, N),
                np.full(N, Q - 1)):
        idx = jnp.asarray(idx, jnp.int32)
        got = queues.slot_read(x, queues.slot_mask(idx, Q))
        assert_same(got, x[jnp.arange(N), idx])


# ---------------------------------------------------------------------------
# structure: no indexed access to an [n, Q] field is left
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("traced", [False, True])
def test_no_indexed_access_to_slot_fields(traced):
    st, cfg, rng = _state(0, "random", traced)
    mask = _mask(rng, "random")
    vec = jnp.ones((N,), jnp.float32)
    extras = ({"src": jnp.arange(N), "energy": vec, "txtime": vec}
              if traced else None)
    profile = make_profile(cfg)
    tgt = jnp.zeros((N,), jnp.int32)
    progs = {
        "push": (lambda s: queues.push(s, mask, vec, vec,
                                       jnp.zeros_like(s["tx_visited"]),
                                       extras)),
        "pop_head": lambda s: queues.pop_head(s, mask),
        "compute_pass": lambda s: _compute_pass(s, vec, vec,
                                                jnp.float32(1.0), cfg),
        "initiate": lambda s: transfer.initiate(s, mask, tgt,
                                                jnp.float32(1.0), profile),
    }
    for name, fn in progs.items():
        shapes = indexed_operand_shapes(fn, st)
        assert (N, Q) not in shapes, name
        if name == "push":     # the visited words are slot fields too
            assert st["q_visited"].shape not in shapes
            assert st["tx_visited"].shape not in shapes
