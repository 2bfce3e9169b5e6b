"""Unit + property tests for the paper's core protocol (Eqs. 9-16)."""
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import (CongestionState, Neighborhood, congestion_update,
                        decision_epoch, exit_accuracy, exit_boundary_layers,
                        exit_label, init_protocol, phi_bounds_ok,
                        phi_fixpoint, phi_update, transfer_decision)

jax.config.update("jax_platform_name", "cpu")


def ring_topology(n, d=1e-3):
    adj = np.zeros((n, n), bool)
    for i in range(n):
        adj[i, (i + 1) % n] = adj[i, (i - 1) % n] = True
    return jnp.asarray(adj), jnp.full((n, n), d, jnp.float32)


# ---------------------------------------------------------------------------
# Eq. 10 — diffusive metric
# ---------------------------------------------------------------------------


def test_phi_isolated_node_equals_local_capability():
    F = jnp.asarray([100.0, 200.0, 300.0])
    adj = jnp.zeros((3, 3), bool)
    d_tx = jnp.zeros((3, 3))
    phi = phi_update(F, F, adj, d_tx)
    np.testing.assert_allclose(np.asarray(phi), np.asarray(F))


def test_phi_converges_geometrically_on_connected_graph():
    """Paper's claim: residuals contract >= 2x per round for |M_i| >= 1."""
    n = 24
    adj, d_tx = ring_topology(n)
    F = jnp.asarray(np.random.default_rng(0).uniform(100, 800, n),
                    jnp.float32)
    phi, residuals = phi_fixpoint(F, adj, d_tx, iters=20)
    res = np.asarray(residuals)
    # after a couple of rounds residual strictly decays; final ~ 0
    assert res[-1] < 1e-6
    late = res[3:12]
    ratios = late[1:] / np.maximum(late[:-1], 1e-30)
    assert np.all(ratios < 0.75), ratios


def test_phi_bounds_invariant():
    n = 16
    rng = np.random.default_rng(1)
    adj = rng.uniform(size=(n, n)) < 0.4
    adj = np.logical_and(adj, ~np.eye(n, dtype=bool))
    F = jnp.asarray(rng.uniform(100, 500, n), jnp.float32)
    d_tx = jnp.where(jnp.asarray(adj), 1e-3, -1e30)
    phi, _ = phi_fixpoint(F, jnp.asarray(adj), d_tx, iters=16)
    assert bool(phi_bounds_ok(phi, F, jnp.asarray(adj)))


def test_phi_prefers_fast_neighborhoods():
    """A node with strong neighbors must end with higher φ than an identical
    node with weak neighbors (the metric's whole point)."""
    # star A: center 0 with strong leaves; star B: center 3 with weak leaves
    F = jnp.asarray([200.0, 800.0, 800.0, 200.0, 50.0, 50.0], jnp.float32)
    adj = np.zeros((6, 6), bool)
    adj[0, 1] = adj[1, 0] = adj[0, 2] = adj[2, 0] = True
    adj[3, 4] = adj[4, 3] = adj[3, 5] = adj[5, 3] = True
    d_tx = jnp.where(jnp.asarray(adj), 1e-4, -1e30)
    phi, _ = phi_fixpoint(F, jnp.asarray(adj), d_tx, iters=16)
    assert float(phi[0]) > float(phi[3])


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(3, 20),
    seed=st.integers(0, 2**31 - 1),
)
def test_phi_update_positive_and_finite(n, seed):
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.uniform(50, 1000, n), jnp.float32)
    adj = rng.uniform(size=(n, n)) < 0.5
    adj = np.logical_and(adj, ~np.eye(n, dtype=bool))
    d_tx = jnp.where(jnp.asarray(adj),
                     jnp.asarray(rng.uniform(1e-5, 1e-2, (n, n)),
                                 jnp.float32), -1e30)
    phi = F
    for _ in range(5):
        phi = phi_update(phi, F, jnp.asarray(adj), d_tx)
        a = np.asarray(phi)
        assert np.all(np.isfinite(a)) and np.all(a > 0)


# ---------------------------------------------------------------------------
# Eqs. 11-13 — transfer decision
# ---------------------------------------------------------------------------


def test_transfer_picks_least_utilized_neighbor_and_respects_gamma():
    phi = jnp.asarray([100.0, 100.0, 100.0])
    T = jnp.asarray([50.0, 10.0, 30.0])      # U = [.5, .1, .3]
    adj = jnp.asarray(~np.eye(3, dtype=bool))
    dec = transfer_decision(T, phi, Neighborhood(adj), gamma=0.1)
    assert int(dec.target[0]) == 1           # least utilized neighbor
    assert bool(dec.transfer[0])             # 0.5 - 0.1 > γ
    assert not bool(dec.transfer[1])         # already the least utilized
    # γ hysteresis: huge γ → nobody transfers
    dec2 = transfer_decision(T, phi, Neighborhood(adj), gamma=10.0)
    assert not bool(jnp.any(dec2.transfer))


def test_no_neighbors_means_no_transfer():
    dec = transfer_decision(jnp.asarray([99.0]), jnp.asarray([1.0]),
                            Neighborhood(jnp.zeros((1, 1), bool)), gamma=0.0)
    assert not bool(dec.transfer[0]) and int(dec.target[0]) == -1


# ---------------------------------------------------------------------------
# Eqs. 14-16 — congestion-aware early exit
# ---------------------------------------------------------------------------


def test_congestion_ema_and_labels():
    st0 = CongestionState(jnp.zeros((1,)), jnp.zeros((1,)))
    # queue grows by 1 GFLOP per 0.2 s epoch => dT/dt = 5
    s = st0
    for k in range(1, 30):
        s = congestion_update(s, jnp.asarray([float(k)]), 0.2, 0.3)
    assert abs(float(s.D[0]) - 5.0) < 0.1    # EMA converges to the true slope
    lbl = exit_label(s.D, 1.5, 2.5)
    assert int(lbl[0]) == 2                  # high congestion
    lbl2 = exit_label(jnp.asarray([2.0]), 1.5, 2.5)
    assert int(lbl2[0]) == 1                 # medium
    lbl3 = exit_label(jnp.asarray([0.0]), 1.5, 2.5)
    assert int(lbl3[0]) == 0


def test_exit_boundaries_and_accuracy_levels():
    pts = (15, 30, 60)
    layers = exit_boundary_layers(jnp.asarray([0, 1, 2]), pts, 3)
    np.testing.assert_array_equal(np.asarray(layers), [60, 33, 18])
    acc = exit_accuracy(jnp.asarray([0, 1, 2]), (0.6, 0.9, 0.95))
    np.testing.assert_allclose(np.asarray(acc), [0.95, 0.9, 0.6])


def test_exit_boundary_layers_pins_table2_mapping():
    """Table 2 label→layers mapping, pinned against the default config:
    truncation depth decreases as congestion rises (full → L2+3 → L1+3)."""
    from repro.configs.base import SwarmConfig
    cfg = SwarmConfig()
    L1, L2, L_full = cfg.exit_points
    fin = cfg.exit_finalize_layers
    layers = exit_boundary_layers(jnp.asarray([0, 1, 2]), cfg.exit_points,
                                  fin)
    np.testing.assert_array_equal(
        np.asarray(layers), [L_full, L2 + fin, L1 + fin])   # 60 / 33 / 18
    # finalize layers can never push a truncated exit past the full network
    capped = exit_boundary_layers(jnp.asarray([1, 2]), (59, 59, 60), 3)
    np.testing.assert_array_equal(np.asarray(capped), [60, 60])


# ---------------------------------------------------------------------------
# Alg. 1 — composed epoch
# ---------------------------------------------------------------------------


def test_decision_epoch_runs_and_is_consistent():
    n = 8
    rng = np.random.default_rng(2)
    F = jnp.asarray(rng.uniform(100, 500, n), jnp.float32)
    adj, d_tx = ring_topology(n)
    state = init_protocol(F)
    out = decision_epoch(
        state, F=F, nbhd=Neighborhood(adj), d_tx=d_tx,
        queued_gflops=jnp.asarray(rng.uniform(0, 100, n), jnp.float32),
        gamma=0.02, dt=0.2, alpha=0.3, tau_med=1.5, tau_high=2.5,
        exit_points=(15, 30, 60), finalize_layers=3)
    assert out.exit_layers.shape == (n,)
    assert bool(jnp.all(out.state.phi > 0))
    # transfers only point at actual neighbors
    tgt = np.asarray(out.decision.target)
    tr = np.asarray(out.decision.transfer)
    adj_np = np.asarray(adj)
    for i in range(n):
        if tr[i]:
            assert adj_np[i, tgt[i]]
