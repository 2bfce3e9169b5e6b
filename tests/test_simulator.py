"""Swarm simulator invariants + paper-claim checks (integration level)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.swarm import (DISTRIBUTED, GREEDY, LOCAL_ONLY, RANDOM,
                         RANDOM_ACYCLIC, make_profile, run_many)

CFG = dataclasses.replace(SwarmConfig(), sim_time_s=20.0, num_workers=15)
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def results():
    out = {}
    for s in (LOCAL_ONLY, RANDOM, RANDOM_ACYCLIC, GREEDY, DISTRIBUTED):
        out[s] = run_many(KEY, CFG, jnp.int32(s), 15, 6)
    return out


def test_task_conservation(results):
    """generated = completed + remaining-in-system + dropped (approximately:
    remaining is measured in GFLOPs, so convert via the task profile)."""
    profile = make_profile(CFG)
    for m in results.values():
        gen = np.asarray(m["generated"])
        done = np.asarray(m["completed"])
        drop = np.asarray(m["dropped"])
        rem_tasks = np.asarray(m["remaining_gflops"]) / profile.total_gflops
        # remaining GFLOPs undercounts partially-done tasks ⇒ inequality both
        # ways with a 1-task-per-node slack
        assert np.all(done + drop <= gen + 1e-3)
        assert np.all(gen - done - drop <= rem_tasks + CFG.num_workers + 1)


def test_local_only_never_transfers(results):
    assert float(np.max(np.asarray(results[LOCAL_ONLY]["transfers"]))) == 0.0


def test_energy_positive_and_accounted(results):
    for s, m in results.items():
        assert np.all(np.asarray(m["energy_total_j"]) > 0)
        if s == LOCAL_ONLY:
            # no transfers => no tx energy => lowest energy per processed task
            pass
    e_local = np.asarray(results[LOCAL_ONLY]["energy_per_task_j"]).mean()
    e_dist = np.asarray(results[DISTRIBUTED]["energy_per_task_j"]).mean()
    assert e_local <= e_dist + 1e-6   # paper Fig. 4e: LocalOnly cheapest


def test_fairness_in_unit_interval(results):
    for m in results.values():
        j = np.asarray(m["jain_fairness"])
        assert np.all((j > 0) & (j <= 1.0 + 1e-6))


def test_distributed_beats_local_under_load(results):
    """Paper Fig. 4: the diffusive method completes more work with lower
    latency than LocalOnly in the bursty default regime."""
    lat_d = float(np.asarray(results[DISTRIBUTED]["avg_latency_s"]).mean())
    lat_l = float(np.asarray(results[LOCAL_ONLY]["avg_latency_s"]).mean())
    rem_d = float(np.asarray(results[DISTRIBUTED]["remaining_gflops"]).mean())
    rem_l = float(np.asarray(results[LOCAL_ONLY]["remaining_gflops"]).mean())
    assert lat_d < lat_l
    assert rem_d < rem_l


def test_distributed_transfers_bounded(results):
    """One outgoing transfer per node at a time: transfers per node per
    decision epoch <= 1."""
    n_epochs = CFG.sim_time_s / CFG.decision_period_s
    tx = np.asarray(results[DISTRIBUTED]["transfers"])
    assert np.all(tx <= CFG.num_workers * n_epochs)


def test_early_exit_reduces_latency_and_accuracy():
    cfg_ee = dataclasses.replace(CFG, early_exit_enabled=True)
    m_off = run_many(KEY, CFG, jnp.int32(DISTRIBUTED), 15, 6)
    m_on = run_many(KEY, cfg_ee, jnp.int32(DISTRIBUTED), 15, 6)
    assert (np.asarray(m_on["avg_latency_s"]).mean()
            < np.asarray(m_off["avg_latency_s"]).mean())
    assert (np.asarray(m_on["avg_accuracy"]).mean()
            <= np.asarray(m_off["avg_accuracy"]).mean() + 1e-6)
    # with early exit off, completed tasks carry full accuracy
    np.testing.assert_allclose(np.asarray(m_off["avg_accuracy"]), 0.95,
                               atol=1e-3)


def test_channel_monotonicity():
    from repro.swarm.channel import capacity_bps, snr_db, two_ray_pathloss_db
    d = jnp.asarray([100.0, 1_000.0, 5_000.0, 20_000.0])
    pl = two_ray_pathloss_db(d, 100.0, 100.0)
    assert bool(jnp.all(jnp.diff(pl) > 0))          # loss grows with distance
    s = snr_db(d[None], SwarmConfig())
    assert bool(jnp.all(jnp.diff(s[0]) < 0))        # SNR falls
    c = capacity_bps(s, SwarmConfig())
    assert bool(jnp.all(jnp.diff(c[0]) < 0))        # capacity falls


def test_mobility_stays_on_circle():
    from repro.swarm.mobility import init_mobility, positions_at
    cfg = SwarmConfig()
    mob = init_mobility(jax.random.PRNGKey(3), cfg, 10)
    p0 = positions_at(mob, cfg, 0.0)
    p1 = positions_at(mob, cfg, 12.345)
    r0 = jnp.linalg.norm(p0 - mob["center"], axis=-1)
    r1 = jnp.linalg.norm(p1 - mob["center"], axis=-1)
    np.testing.assert_allclose(np.asarray(r0), cfg.movement_radius_m,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r1), cfg.movement_radius_m,
                               rtol=1e-5)
    # speed check: arc length over dt
    dt = 0.1
    p2 = positions_at(mob, cfg, 12.345 + dt)
    v = jnp.linalg.norm(p2 - p1, axis=-1) / dt
    np.testing.assert_allclose(np.asarray(v), cfg.speed_mps, rtol=1e-3)


# ---------------------------------------------------------------------------
# RNG stream pin (swarmlint R001 audit, DESIGN.md §13)
# ---------------------------------------------------------------------------

# Exact float32 goldens (hex, lossless) for the default scenario after the
# init_state key fix: kf/km/k_fault now come from one split(key, 3) instead
# of split(key) + fold_in(key, 7).  Regenerated under jax 0.9.0, whose
# default jax_threefry_partitionable=True draws different random bits from
# the same keys (the simulator's key derivations did not change).  Any
# change to the key derivations in init_state/_epoch — including
# "harmless" re-splits of the sites baselined in analysis_baseline.toml —
# moves these streams and must be deliberate: regenerate the table AND
# bump the result-store code version in the same change, or cached sweep
# points will silently alias the old streams (the store digest carries the
# jax version for the same reason).
_RNG_PIN = {
    LOCAL_ONLY: {
        "completed": "0x1.3ce0000000000p+11",
        "generated": "0x1.5100000000000p+11",
        "avg_latency_s": "0x1.d29ae40000000p-1",
        "energy_total_j": "0x1.306b000000000p+9",
        "jain_fairness": "0x1.3334660000000p-1",
        "transfers_delivered": "0x0.0p+0",
    },
    GREEDY: {
        "completed": "0x1.3d20000000000p+11",
        "generated": "0x1.5100000000000p+11",
        "avg_latency_s": "0x1.cd0ffe0000000p-1",
        "energy_total_j": "0x1.3154080000000p+9",
        "jain_fairness": "0x1.3599940000000p-1",
        "transfers_delivered": "0x1.6000000000000p+4",
    },
    DISTRIBUTED: {
        "completed": "0x1.4040000000000p+11",
        "generated": "0x1.5100000000000p+11",
        "avg_latency_s": "0x1.6ebdce0000000p-1",
        "energy_total_j": "0x1.3eeff80000000p+9",
        "jain_fairness": "0x1.4bd4cc0000000p-1",
        "transfers_delivered": "0x1.7b00000000000p+8",
    },
}


@pytest.mark.parametrize("strategy", sorted(_RNG_PIN))
def test_default_scenario_rng_pin(strategy):
    """Bit-identity golden for the default scenario's RNG streams.

    Referenced by analysis_baseline.toml and DESIGN.md §13.2: the R001
    baseline entries assert their key derivations are *deliberate*; this
    test is what makes that assertion checkable.  A failure here means a
    key derivation (or any traced arithmetic) changed the simulated
    numbers — never "fix" it by regenerating the goldens without also
    retiring the cached store entries (REPRO_CODE_VERSION / code bump).
    """
    from repro.swarm.simulator import run_sim
    m = jax.jit(lambda k: run_sim(k, CFG, jnp.int32(strategy),
                                  CFG.num_workers))(KEY)
    for k, hexval in _RNG_PIN[strategy].items():
        got = float(np.asarray(m[k]))
        assert got.hex() == hexval, (
            f"{k}: {got.hex()} != pinned {hexval} — RNG stream or traced "
            f"arithmetic moved (see DESIGN.md §13.2 before regenerating)")
