"""Sparse neighbor-list φ path tests (DESIGN.md §11).

Three layers of parity, each pinned exactly:

  * the spatial-hash neighbor builder against an O(N²) brute force
    (coverage is provable when the cell edge >= the candidate radius);
  * the gather-based Pallas kernel (interpret mode) against the jnp
    reference, including padded / multi-tile shapes;
  * the whole sparse epoch pipeline — per-edge channel, φ update,
    offload decisions — against the dense [N, N] path, bit-for-bit,
    whenever ``neighbor_k`` covers the true max degree.

The e2e equivalence holds for the deterministic channels and the
LocalOnly/Greedy/Distributed strategies; Random/RandomAcyclic draw their
target gumbels over [N, K] instead of [N, N] (an intentional stream
divergence, exercised for sanity only), and the stochastic channels draw
per-edge rather than per-matrix (symmetry + self-consistency pinned
instead).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core.diffusive import (NEG, phi_update, phi_update_op,
                                  phi_update_sparse)
from repro.core.neighborhood import Neighborhood
from repro.fleet import run_batch
from repro.kernels import ref
from repro.kernels.diffusive_phi import \
    diffusive_phi_sparse as pl_phi_sparse
from repro.swarm import (DISTRIBUTED, GREEDY, LOCAL_ONLY, RANDOM,
                         RANDOM_ACYCLIC, comm_range_m, get_channel_edges,
                         grid_geometry, neighbor_lists, run_many)
from repro.swarm.channel import (edge_rate, link_state, link_state_sparse,
                                 pairwise_distance)

KEY = jax.random.PRNGKey(0)

# small swarm where K = N - 1 covers any degree: the exact-parity regime
N, RUNS = 12, 3
CFG = dataclasses.replace(SwarmConfig(), sim_time_s=5.0, num_workers=N)
CFG_SP = dataclasses.replace(CFG, neighbor_mode="sparse", neighbor_k=N - 1)


def _np(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


# ---------------------------------------------------------------------------
# neighbor builder vs brute force
# ---------------------------------------------------------------------------


def test_neighbor_lists_match_brute_force_radius():
    """Radius-limited regime (cell >= range): the lists must hold *exactly*
    the within-range node sets, in ascending id order."""
    n, r = 64, 3000.0
    cfg = dataclasses.replace(SwarmConfig(), neighbor_range_m=r,
                              neighbor_k=n - 1)
    pos = jax.random.uniform(KEY, (n, 2), jnp.float32, 0.0, cfg.area_m)
    nbr, valid = neighbor_lists(pos, cfg)
    nbr, valid = np.asarray(nbr), np.asarray(valid)
    d = np.asarray(pairwise_distance(pos))
    within = (d <= r) & ~np.eye(n, dtype=bool)
    for i in range(n):
        got = nbr[i, valid[i]]
        assert sorted(got) == list(got), f"node {i} not id-sorted"
        assert set(got.tolist()) == set(np.where(within[i])[0].tolist()), i
    # invalid slots are index 0 (masked downstream), pushed to the end
    assert np.all(nbr[~valid] == 0)


def test_neighbor_lists_keep_k_nearest():
    """K < degree: the kept neighbors are the K nearest within range."""
    n, k, r = 200, 8, 2000.0
    cfg = dataclasses.replace(SwarmConfig(), neighbor_range_m=r,
                              neighbor_k=k)
    pos = jax.random.uniform(jax.random.fold_in(KEY, 1), (n, 2),
                             jnp.float32, 0.0, cfg.area_m)
    nbr, valid = neighbor_lists(pos, cfg)
    nbr, valid = np.asarray(nbr), np.asarray(valid)
    d = np.asarray(pairwise_distance(pos)).copy()
    d[np.eye(n, dtype=bool)] = np.inf
    d[d > r] = np.inf
    for i in range(n):
        finite = np.isfinite(d[i]).sum()
        want = set(np.argsort(d[i])[:min(k, finite)].tolist())
        assert set(nbr[i, valid[i]].tolist()) == want, i
        assert valid[i].sum() == min(k, finite)


def test_grid_geometry_is_static_and_covering():
    cfg = dataclasses.replace(SwarmConfig(), neighbor_range_m=3000.0)
    G, cell, cap = grid_geometry(cfg, 64, 16)
    assert isinstance(G, int) and isinstance(cap, int)
    assert isinstance(cell, float)
    # floor-derived grid: realized cell never shrinks below the range, so
    # the 3x3 window provably covers every in-range neighbor
    assert cell >= comm_range_m(cfg)
    assert cap == 64            # small swarms: exact (cap = n)
    G2, _, cap2 = grid_geometry(cfg, 65_536, 16)
    assert G2 > 1 and cap2 < 65_536


def test_comm_range_override_and_default():
    cfg = SwarmConfig()
    diag = cfg.area_m * np.sqrt(2.0)
    assert comm_range_m(cfg) == pytest.approx(diag)   # two-ray reaches far
    cfg_r = dataclasses.replace(cfg, neighbor_range_m=1234.0)
    assert comm_range_m(cfg_r) == 1234.0


# ---------------------------------------------------------------------------
# sparse kernel: interpret-mode Pallas vs jnp reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r,n,k", [(1, 64, 8),    # single tile
                                   (2, 40, 37),   # padded N and K
                                   (1, 100, 1),   # degenerate K
                                   (1, 40, 130)])  # K spans two BK tiles
def test_sparse_kernel_interpret_matches_ref(r, n, k):
    kk = jax.random.split(jax.random.fold_in(KEY, n * 1000 + k), 3)
    F = jax.random.uniform(kk[0], (r, n), jnp.float32, 100, 500)
    nbr = jax.random.randint(kk[1], (r, n, k), 0, n)
    ok = jax.random.bernoulli(kk[2], 0.6, (r, n, k))
    dtx = jnp.where(ok, jax.random.uniform(kk[2], (r, n, k),
                                           jnp.float32, 1e-4, 1e-2), NEG)
    want = ref.diffusive_phi_sparse(1.0 / F, F, dtx, nbr)
    got = pl_phi_sparse(1.0 / F, F, dtx, nbr, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sparse_kernel_isolated_fallback():
    """Zero-degree rows fall back to phi = F (the Eq. 10 convention the
    dense kernel pins)."""
    F = jnp.full((1, 8), 250.0)
    nbr = jnp.zeros((1, 8, 4), jnp.int32)
    dtx = jnp.full((1, 8, 4), NEG)
    out = pl_phi_sparse(1.0 / F, F, dtx, nbr, interpret=True)
    np.testing.assert_allclose(np.asarray(1.0 / out), np.full((1, 8), 250.0))


# ---------------------------------------------------------------------------
# sparse φ update vs dense, through the channel
# ---------------------------------------------------------------------------


def _sparse_epoch_inputs(cfg, n, key):
    pos = jax.random.uniform(key, (n, 2), jnp.float32, 0.0, cfg.area_m)
    adj, cap = link_state(pos, cfg)
    nbr, valid = neighbor_lists(pos, cfg, k=n - 1)
    adj_e, cap_e = link_state_sparse(pos, nbr, valid, cfg)
    return pos, (adj, cap), (nbr, valid, adj_e, cap_e)


def test_link_state_sparse_matches_dense_entries():
    cfg = SwarmConfig()
    n = 20
    _, (adj, cap), (nbr, valid, adj_e, cap_e) = _sparse_epoch_inputs(
        cfg, n, KEY)
    adj, cap = np.asarray(adj), np.asarray(cap)
    nbr, valid = np.asarray(nbr), np.asarray(valid)
    adj_e, cap_e = np.asarray(adj_e), np.asarray(cap_e)
    for i in range(n):
        # every dense neighbor appears in the list (K = n-1 covers all) …
        assert set(np.where(adj[i])[0]) <= set(nbr[i, valid[i]].tolist())
        for s in range(n - 1):
            if valid[i, s]:
                # … and gathered entries agree exactly
                assert adj_e[i, s] == adj[i, nbr[i, s]]
                if adj_e[i, s]:
                    assert cap_e[i, s] == cap[i, nbr[i, s]]


def test_phi_update_sparse_bitwise_matches_dense():
    cfg = SwarmConfig()
    n = 20
    bpg = 1.0e4
    _, (adj, cap), (nbr, valid, adj_e, cap_e) = _sparse_epoch_inputs(
        cfg, n, KEY)
    F = jax.random.uniform(jax.random.fold_in(KEY, 2), (n,),
                           jnp.float32, 100, 500)
    dtx = jnp.where(adj, bpg / cap, 1e30)
    dtx_e = jnp.where(adj_e, bpg / cap_e, 1e30)
    dense = phi_update(F, F, adj, dtx)
    sparse = phi_update_sparse(F, F, adj_e, nbr, dtx_e)
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(dense))
    dense_op = phi_update_op(F, F, Neighborhood(adj), dtx)
    sparse_op = phi_update_op(F, F, Neighborhood(adj_e, nbr), dtx_e)
    np.testing.assert_array_equal(np.asarray(sparse_op),
                                  np.asarray(dense_op))


# ---------------------------------------------------------------------------
# end-to-end: sparse simulator == dense simulator (K >= max degree)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", [LOCAL_ONLY, GREEDY, DISTRIBUTED])
def test_e2e_sparse_matches_dense_exactly(strategy):
    dense = _np(run_many(KEY, CFG, jnp.int32(strategy), N, RUNS))
    sparse = _np(run_many(KEY, CFG_SP, jnp.int32(strategy), N, RUNS))
    assert sorted(dense) == sorted(sparse)
    for k in dense:
        np.testing.assert_array_equal(sparse[k], dense[k], err_msg=k)


@pytest.mark.parametrize("backend,kw", [("sharded", {}),
                                        ("streaming", {"chunk_size": 2})])
def test_sparse_bit_identical_across_backends(backend, kw):
    want = _np(run_batch(KEY, CFG_SP, jnp.int32(DISTRIBUTED), N, RUNS))
    got = _np(run_batch(KEY, CFG_SP, jnp.int32(DISTRIBUTED), N, RUNS,
                        backend=backend, **kw))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("strategy", [RANDOM, RANDOM_ACYCLIC])
def test_sparse_random_strategies_are_sane(strategy):
    """Random/RandomAcyclic sample targets over the [N, K] lists — a
    different gumbel stream than dense [N, N] (documented divergence), so
    pin physics, not parity."""
    m = _np(run_many(KEY, CFG_SP, jnp.int32(strategy), N, RUNS))
    assert np.all(np.isfinite(m["avg_latency_s"]))
    assert np.all(m["generated"] > 0)
    assert np.all(m["completed"] + m["dropped"] <= m["generated"] + 1e-3)
    assert np.all(m["energy_total_j"] > 0)
    assert np.all(m["transfers_delivered"] <= m["transfers"])


def test_sparse_stochastic_channel_runs():
    """Per-edge stochastic channels (different draw stream than dense, by
    design) still produce a physical simulation."""
    cfg = dataclasses.replace(CFG_SP, channel_model="log_normal")
    m = _np(run_many(KEY, cfg, jnp.int32(DISTRIBUTED), N, RUNS))
    assert np.all(np.isfinite(m["avg_latency_s"]))
    assert np.all(m["energy_total_j"] > 0)


# ---------------------------------------------------------------------------
# per-edge channel draws: symmetry, self-consistency, fail-loud coverage
# ---------------------------------------------------------------------------


def test_edge_draws_are_symmetric():
    """Gain draw on (i, j) must equal the draw on (j, i) — the sparse twin
    of the dense models' matrix symmetrization."""
    from repro.swarm.channel import log_normal_edges, nakagami_edges
    cfg = SwarmConfig()
    key = jax.random.fold_in(KEY, 7)
    src = jnp.asarray([[0, 3, 5]], jnp.int32)
    dst = jnp.asarray([[3, 0, 2]], jnp.int32)
    d = jnp.full((1, 3), 800.0, jnp.float32)
    for fn in (log_normal_edges, nakagami_edges):
        pl = np.asarray(fn(key, d, src, dst, cfg))
        assert pl[0, 0] == pl[0, 1], fn.__name__   # (0,3) == (3,0)
        assert pl[0, 0] != pl[0, 2], fn.__name__   # distinct edges differ


def test_edge_rate_consistent_with_link_state_sparse():
    """The per-tick [N] rate vector and the per-epoch [N, K] capacity table
    gather the *same* per-edge draw for the same (src, dst) pair."""
    cfg = dataclasses.replace(SwarmConfig(), neighbor_mode="sparse",
                              channel_model="log_normal")
    edge_fn = get_channel_edges(cfg)
    n = 16
    key = jax.random.fold_in(KEY, 11)
    pos = jax.random.uniform(key, (n, 2), jnp.float32, 0.0, cfg.area_m)
    nbr, valid = neighbor_lists(pos, cfg, k=n - 1)
    adj_e, cap_e = link_state_sparse(pos, nbr, valid, cfg, key=key,
                                     pathloss_fn=edge_fn)
    # each node targets its first listed neighbor (itself when isolated)
    dst = jnp.where(valid[:, 0], nbr[:, 0], jnp.arange(n))
    rate = edge_rate(pos, dst, cfg, key=key, pathloss_fn=edge_fn)
    want = jnp.where(adj_e[:, 0] & valid[:, 0], cap_e[:, 0], 1.0)
    np.testing.assert_array_equal(np.asarray(rate), np.asarray(want))


def test_unported_channel_fails_loud_in_sparse_mode():
    """log_normal_corr has no per-edge twin (its Gudmundson field is
    inherently O(N²)); sparse mode must refuse it, not silently diverge."""
    cfg = dataclasses.replace(CFG_SP, channel_model="log_normal_corr")
    with pytest.raises(KeyError, match="log_normal_corr"):
        run_many(KEY, cfg, jnp.int32(DISTRIBUTED), N, 1)
