"""Scenario-engine tests: registries, mobility/channel model contracts,
fault-injector invariants, and the Pallas φ-kernel parity through the
simulator path (DESIGN.md §3.4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.core.diffusive import phi_update, phi_update_op
from repro.core.neighborhood import Neighborhood
from repro.swarm import (CHANNEL_MODELS, DISTRIBUTED, FAULT_MODELS,
                         MOBILITY_MODELS, get_channel, get_fault,
                         get_mobility, make_profile, run_many)
from repro.swarm.channel import link_state

KEY = jax.random.PRNGKey(0)
N = 12


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------


def test_registry_roundtrip():
    assert set(MOBILITY_MODELS) == {"circular", "random_waypoint",
                                    "gauss_markov", "levy_flight"}
    assert set(CHANNEL_MODELS) == {"two_ray", "free_space", "log_normal",
                                   "log_normal_corr", "rician", "nakagami"}
    assert set(FAULT_MODELS) == {"none", "markov"}
    for name in MOBILITY_MODELS:
        cfg = dataclasses.replace(SwarmConfig(), mobility_model=name)
        assert get_mobility(cfg) is MOBILITY_MODELS[name]
    for name in CHANNEL_MODELS:
        cfg = dataclasses.replace(SwarmConfig(), channel_model=name)
        assert get_channel(cfg) is CHANNEL_MODELS[name]
    for name in FAULT_MODELS:
        cfg = dataclasses.replace(SwarmConfig(), fault_model=name)
        assert get_fault(cfg) is FAULT_MODELS[name]


def test_registry_unknown_key_raises_with_known_keys():
    cfg = dataclasses.replace(SwarmConfig(), mobility_model="brownian")
    with pytest.raises(KeyError, match="circular"):
        get_mobility(cfg)
    cfg = dataclasses.replace(SwarmConfig(), channel_model="weibull")
    with pytest.raises(KeyError, match="two_ray"):
        get_channel(cfg)
    cfg = dataclasses.replace(SwarmConfig(), fault_model="byzantine")
    with pytest.raises(KeyError, match="markov"):
        get_fault(cfg)


# ---------------------------------------------------------------------------
# mobility models: shapes, finiteness, area containment
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["circular", "random_waypoint",
                                  "gauss_markov", "levy_flight"])
def test_mobility_shapes_and_finiteness(name):
    cfg = dataclasses.replace(SwarmConfig(), mobility_model=name)
    model = get_mobility(cfg)
    state = model.init(KEY, cfg, N)
    for i in range(30):
        k = jax.random.fold_in(KEY, i)
        t0 = i * cfg.decision_period_s
        state, pos = model.step(state, k, cfg, jnp.float32(t0))
        assert pos.shape == (N, 2)
        assert bool(jnp.all(jnp.isfinite(pos)))
        if name != "circular":   # orbits may overhang grid-cell centers
            assert bool(jnp.all((pos >= 0.0) & (pos <= cfg.area_m)))


def test_random_waypoint_respects_speed_bound():
    cfg = dataclasses.replace(SwarmConfig(), mobility_model="random_waypoint")
    model = get_mobility(cfg)
    state = model.init(KEY, cfg, N)
    state, prev = model.step(state, KEY, cfg, jnp.float32(0.0))
    for i in range(1, 11):
        state, pos = model.step(state, jax.random.fold_in(KEY, i), cfg,
                                jnp.float32(i * cfg.decision_period_s))
        hop = np.asarray(jnp.linalg.norm(pos - prev, axis=-1))
        assert np.all(hop <= cfg.speed_max_mps * cfg.decision_period_s
                      + 1e-3)
        assert np.any(hop > 0)                           # it does move
        prev = pos


@pytest.mark.parametrize("name", ["random_waypoint", "gauss_markov",
                                  "levy_flight"])
def test_stepped_mobility_epoch0_returns_initial_placement(name):
    """Epoch-start contract: the t0 = 0 step observes the init placement
    (no one-period phase offset vs the closed-form circular model)."""
    cfg = dataclasses.replace(SwarmConfig(), mobility_model=name)
    model = get_mobility(cfg)
    state = model.init(KEY, cfg, N)
    init_pos = np.asarray(state["pos"])
    _, pos = model.step(state, jax.random.fold_in(KEY, 99), cfg,
                        jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(pos), init_pos)


# ---------------------------------------------------------------------------
# channel models: finiteness, symmetry, monotone deterministic pathloss
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["two_ray", "free_space", "log_normal",
                                  "log_normal_corr", "rician", "nakagami"])
def test_channel_link_state_contract(name):
    cfg = dataclasses.replace(SwarmConfig(), channel_model=name)
    pos = jax.random.uniform(KEY, (N, 2), jnp.float32, 0.0, cfg.area_m)
    adj, cap = link_state(pos, cfg, key=KEY, pathloss_fn=get_channel(cfg))
    assert adj.shape == (N, N) and cap.shape == (N, N)
    assert not bool(jnp.any(jnp.diag(adj)))              # no self links
    assert bool(jnp.all(cap > 0.0))                      # safe divisor
    assert bool(jnp.all(jnp.isfinite(cap)))
    # symmetric pathloss => symmetric adjacency (same key both directions)
    np.testing.assert_array_equal(np.asarray(adj), np.asarray(adj).T)


@pytest.mark.parametrize("name", ["two_ray", "free_space"])
def test_deterministic_pathloss_monotone_in_distance(name):
    cfg = dataclasses.replace(SwarmConfig(), channel_model=name)
    fn = get_channel(cfg)
    d = jnp.asarray([[10.0, 100.0, 1_000.0, 10_000.0]])
    pl = np.asarray(fn(KEY, d, cfg))[0]
    assert np.all(np.diff(pl) > 0)


@pytest.mark.parametrize("name", ["log_normal", "log_normal_corr", "rician",
                                  "nakagami"])
def test_stochastic_channel_varies_with_key_but_not_baseline(name):
    cfg = SwarmConfig()
    fn = CHANNEL_MODELS[name]
    d = jnp.full((4, 4), 2_000.0)
    pl1 = np.asarray(fn(jax.random.PRNGKey(1), d, cfg))
    pl2 = np.asarray(fn(jax.random.PRNGKey(2), d, cfg))
    off = ~np.eye(4, dtype=bool)
    assert not np.allclose(pl1[off], pl2[off])           # epoch redraw
    np.testing.assert_array_equal(np.diag(pl1), np.diag(pl2))
    np.testing.assert_allclose(pl1, pl1.T)               # symmetric links


@pytest.mark.parametrize("name", ["rician", "nakagami"])
def test_fading_gain_is_unit_mean_around_log_distance_baseline(name):
    """Small-scale fading redistributes SNR but adds no systematic loss:
    the mean linear power gain 10^((base - PL)/10) over many links is 1."""
    cfg = SwarmConfig()
    from repro.swarm.channel import _log_distance_db
    n = 200
    d = jnp.full((n, n), 2_000.0)
    pl = np.asarray(CHANNEL_MODELS[name](KEY, d, cfg))
    base = float(np.asarray(_log_distance_db(jnp.float32(2_000.0), cfg)))
    g = 10.0 ** ((base - pl) / 10.0)
    off = ~np.eye(n, dtype=bool)
    assert abs(g[off].mean() - 1.0) < 0.05
    assert g[off].std() > 0.05                           # it does fade


def test_correlated_shadowing_follows_gudmundson_decorrelation():
    """log_normal_corr contract: links between distinct endpoint pairs are
    strongly correlated when the endpoints sit within the decorrelation
    distance and (near-)independent far outside it, while every link keeps
    the iid model's marginal N(0, σ²)."""
    import dataclasses as dc
    from repro.swarm.channel import _log_distance_db, pairwise_distance

    # two tight clusters 5 km apart: {0,1} and {2,3}, 10 m inside a cluster
    pos = jnp.asarray([[0.0, 0.0], [10.0, 0.0],
                       [5_000.0, 0.0], [5_010.0, 0.0]], jnp.float32)
    dist = pairwise_distance(pos)
    base = np.asarray(_log_distance_db(dist, SwarmConfig()))
    fn = CHANNEL_MODELS["log_normal_corr"]

    def shadow_samples(corr_m, n_keys=400):
        cfg = dc.replace(SwarmConfig(), shadow_corr_m=corr_m)
        x01, x23 = [], []
        for i in range(n_keys):
            x = np.asarray(fn(jax.random.PRNGKey(i), dist, cfg)) - base
            # links (0,2) and (1,3): no shared endpoint
            x01.append(x[0, 2])
            x23.append(x[1, 3])
        return np.asarray(x01), np.asarray(x23)

    a, b = shadow_samples(corr_m=50_000.0)     # swarm-scale correlation
    corr_near = np.corrcoef(a, b)[0, 1]
    assert corr_near > 0.8, corr_near          # clustered endpoints co-shadow
    a, b = shadow_samples(corr_m=1.0)          # decorrelated regime
    corr_far = np.corrcoef(a, b)[0, 1]
    assert abs(corr_far) < 0.3, corr_far
    # exact marginal: every off-diagonal link keeps std sigma
    assert abs(a.std() - SwarmConfig().shadowing_sigma_db) < 1.0


def test_nakagami_concentrates_with_large_m():
    """m → ∞ approaches the deterministic log-distance baseline."""
    cfg_lo = dataclasses.replace(SwarmConfig(), nakagami_m=1.0)
    cfg_hi = dataclasses.replace(SwarmConfig(), nakagami_m=64.0)
    d = jnp.full((64, 64), 2_000.0)
    fn = CHANNEL_MODELS["nakagami"]
    off = ~np.eye(64, dtype=bool)
    spread_lo = np.asarray(fn(KEY, d, cfg_lo))[off].std()
    spread_hi = np.asarray(fn(KEY, d, cfg_hi))[off].std()
    assert spread_hi < spread_lo / 3.0


def test_levy_flight_bounded_and_speed_capped():
    cfg = dataclasses.replace(SwarmConfig(), mobility_model="levy_flight")
    model = get_mobility(cfg)
    state = model.init(KEY, cfg, 64)
    state, prev = model.step(state, KEY, cfg, jnp.float32(0.0))
    hops = []
    for i in range(1, 31):
        state, pos = model.step(state, jax.random.fold_in(KEY, i), cfg,
                                jnp.float32(i * cfg.decision_period_s))
        assert bool(jnp.all((pos >= 0.0) & (pos <= cfg.area_m)))
        hops.append(np.asarray(jnp.linalg.norm(pos - prev, axis=-1)))
        prev = pos
    hops = np.concatenate(hops)
    cap = cfg.speed_max_mps * cfg.decision_period_s
    assert np.all(hops <= cap + 1e-3)        # physical speed cap holds
    assert np.any(hops > 0)                  # it does move
    # heavy tail: long relocations (> half the cap) are rare but present
    frac_long = float(np.mean(hops > 0.5 * cap))
    assert 0.0 < frac_long < 0.5


# ---------------------------------------------------------------------------
# fault injector
# ---------------------------------------------------------------------------


def test_fault_none_is_identity():
    cfg = SwarmConfig()
    model = get_fault(cfg)
    alive = model.init(KEY, cfg, N)
    assert bool(jnp.all(alive))
    assert bool(jnp.all(model.step(alive, KEY, cfg)))


def test_fault_markov_adjacency_invariants():
    cfg = dataclasses.replace(SwarmConfig(), fault_model="markov",
                              fault_mean_up_s=2.0, fault_mean_down_s=2.0)
    model = get_fault(cfg)
    alive = model.init(KEY, cfg, N)
    full = ~jnp.eye(N, dtype=bool)
    seen_down = False
    for i in range(50):
        alive = model.step(alive, jax.random.fold_in(KEY, i), cfg)
        adj = Neighborhood(full).masked(alive).mask
        a = np.asarray(adj)
        al = np.asarray(alive)
        # no edge may touch a down node, in either direction
        assert not np.any(a[~al, :]) and not np.any(a[:, ~al])
        # up-up pairs keep their original links
        np.testing.assert_array_equal(a[np.ix_(al, al)],
                                      np.asarray(full)[np.ix_(al, al)])
        seen_down |= not np.all(al)
    assert seen_down      # symmetric 2 s dwell chain must churn in 50 epochs


def test_churn_preserves_task_conservation():
    """Queued work survives outages: generated = completed + in-system +
    dropped still holds under heavy churn."""
    cfg = dataclasses.replace(SwarmConfig(), sim_time_s=10.0, num_workers=10,
                              fault_model="markov", fault_mean_up_s=3.0,
                              fault_mean_down_s=3.0)
    m = run_many(KEY, cfg, jnp.int32(DISTRIBUTED), 10, 4)
    profile = make_profile(cfg)
    gen = np.asarray(m["generated"])
    done = np.asarray(m["completed"])
    drop = np.asarray(m["dropped"])
    rem_tasks = np.asarray(m["remaining_gflops"]) / profile.total_gflops
    assert np.all(done + drop <= gen + 1e-3)
    assert np.all(gen - done - drop <= rem_tasks + cfg.num_workers + 1)
    # churn slows the swarm down vs the fault-free baseline
    m0 = run_many(KEY, dataclasses.replace(cfg, fault_model="none"),
                  jnp.int32(DISTRIBUTED), 10, 4)
    assert (np.asarray(m["completed"]).mean()
            <= np.asarray(m0["completed"]).mean())


# ---------------------------------------------------------------------------
# scenario sweep smoke: config-only selection through one jitted run_many
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mob,ch,fault", [
    ("random_waypoint", "log_normal", "markov"),
    ("gauss_markov", "free_space", "none"),
])
def test_scenario_selection_is_config_only(mob, ch, fault):
    cfg = dataclasses.replace(SwarmConfig(), sim_time_s=4.0, num_workers=8,
                              mobility_model=mob, channel_model=ch,
                              fault_model=fault)
    for s in range(5):
        m = run_many(KEY, cfg, jnp.int32(s), 8, 2)
        for k, v in m.items():
            assert bool(jnp.all(jnp.isfinite(v))), (s, k)


# ---------------------------------------------------------------------------
# Pallas φ kernel parity (interpret mode) — unit + simulator path
# ---------------------------------------------------------------------------


def _force_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    # the dispatch mode is read at trace time; drop cached executables so
    # the forced mode actually retraces
    jax.clear_caches()


def test_phi_update_op_matches_phi_update_interpret(monkeypatch):
    _force_interpret(monkeypatch)
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    n = 40
    F = jax.random.uniform(k1, (n,), jnp.float32, 100, 500)
    phi = jax.random.uniform(k2, (n,), jnp.float32, 50, 800)
    adj = jax.random.bernoulli(k3, 0.3, (n, n)) & ~jnp.eye(n, dtype=bool)
    d_tx = jnp.where(adj, jax.random.uniform(k4, (n, n), jnp.float32,
                                             1e-4, 1e-2), 1e30)
    want = phi_update(phi, F, adj, d_tx)
    got = phi_update_op(phi, F, Neighborhood(adj), d_tx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    jax.clear_caches()


def test_simulator_phi_kernel_parity_interpret(monkeypatch):
    """Acceptance: the simulator's φ update dispatches through
    kernels/ops.diffusive_phi; interpret-mode Pallas == dense phi_update
    reference through the full run_many path at atol 1e-5."""
    cfg = dataclasses.replace(SwarmConfig(), sim_time_s=4.0, num_workers=10)
    m_ref = run_many(KEY, cfg, jnp.int32(DISTRIBUTED), 10, 2)
    m_ref = {k: np.asarray(v) for k, v in m_ref.items()}
    _force_interpret(monkeypatch)
    m_int = run_many(KEY, cfg, jnp.int32(DISTRIBUTED), 10, 2)
    for k, v in m_int.items():
        np.testing.assert_allclose(np.asarray(v), m_ref[k], atol=1e-5,
                                   rtol=1e-5, err_msg=k)
    jax.clear_caches()
