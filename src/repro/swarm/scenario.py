"""Pluggable scenario engine (DESIGN.md §3.4).

The simulator is parameterized over three orthogonal environment axes, each
selected by a string field on ``SwarmConfig`` through a registry here:

  * **mobility** (``cfg.mobility_model``)  — where the UAVs are each epoch,
  * **channel**  (``cfg.channel_model``)   — pathloss → SNR → adjacency,
  * **fault**    (``cfg.fault_model``)     — epoch-level node up/down churn.

Because the config is static under jit, a scenario sweep is a pure config
change: ``run_many`` compiles one executable per (cfg, n) pair and every
benchmark/example can iterate scenarios without touching simulator code.
Third-party models register with the ``register_*`` decorators; lookups
raise with the list of known keys so a typo'd config fails loudly at trace
time, not with a shape error mid-scan.

The fault injector mirrors ``runtime/fault.py``'s failure-injection idiom
at swarm scale: a two-state Markov chain per node (mean dwell times
``fault_mean_up_s`` / ``fault_mean_down_s``) produces an epoch-level alive
mask that is threaded through adjacency (down nodes have no links), compute
budgets and task arrivals.  Queued work on a down node survives the outage
— conservation invariants hold under churn.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import SwarmConfig
from repro.swarm import channel as _channel
from repro.swarm import mobility as _mobility


class MobilityModel(NamedTuple):
    init: Callable   # (key, cfg, n) -> state pytree
    step: Callable   # (state, key, cfg, t0) -> (state', pos [N, 2])


class FaultModel(NamedTuple):
    init: Callable   # (key, cfg, n) -> alive [N] bool
    step: Callable   # (alive, key, cfg) -> alive' [N] bool


# channel models are bare pathloss callables: (key, dist [N,N], cfg) -> dB;
# edge-channel models are their sparse twins (key, dist [N,K], src [N,K],
# dst [N,K], cfg) -> dB for the neighbor-list path (DESIGN.md §11)
MOBILITY_MODELS: Dict[str, MobilityModel] = {}
CHANNEL_MODELS: Dict[str, Callable] = {}
CHANNEL_EDGE_MODELS: Dict[str, Callable] = {}
FAULT_MODELS: Dict[str, FaultModel] = {}


def _register(registry: Dict, kind: str, name: str, value):
    if name in registry:
        raise ValueError(f"duplicate {kind} model {name!r}")
    registry[name] = value
    return value


def register_mobility(name: str, init: Callable, step: Callable):
    return _register(MOBILITY_MODELS, "mobility", name,
                     MobilityModel(init, step))


def register_channel(name: str, pathloss_fn: Callable):
    return _register(CHANNEL_MODELS, "channel", name, pathloss_fn)


def register_channel_edges(name: str, pathloss_edges_fn: Callable):
    return _register(CHANNEL_EDGE_MODELS, "edge channel", name,
                     pathloss_edges_fn)


def register_fault(name: str, init: Callable, step: Callable):
    return _register(FAULT_MODELS, "fault", name, FaultModel(init, step))


def _lookup(registry: Dict, kind: str, name: str):
    try:
        return registry[name]
    except KeyError:
        raise KeyError(
            f"unknown {kind} model {name!r}; registered: "
            f"{sorted(registry)}") from None


def get_mobility(cfg: SwarmConfig) -> MobilityModel:
    return _lookup(MOBILITY_MODELS, "mobility", cfg.mobility_model)


def get_channel(cfg: SwarmConfig) -> Callable:
    return _lookup(CHANNEL_MODELS, "channel", cfg.channel_model)


def get_fault(cfg: SwarmConfig) -> FaultModel:
    return _lookup(FAULT_MODELS, "fault", cfg.fault_model)


def get_channel_edges(cfg: SwarmConfig) -> Callable:
    """Per-edge pathloss model for ``neighbor_mode="sparse"``.  Channels
    without a sparse implementation (``log_normal_corr`` needs the full
    node-field Cholesky) fail loudly here rather than silently falling
    back to dense."""
    if cfg.channel_model not in CHANNEL_EDGE_MODELS:
        raise KeyError(
            f"channel model {cfg.channel_model!r} has no per-edge (sparse) "
            f"implementation; registered: {sorted(CHANNEL_EDGE_MODELS)} — "
            f"use neighbor_mode='dense' or register_channel_edges()")
    return CHANNEL_EDGE_MODELS[cfg.channel_model]


# ---------------------------------------------------------------------------
# fault/churn models
# ---------------------------------------------------------------------------


def _fault_none_init(key, cfg: SwarmConfig, n: int):
    del key
    return jnp.ones((n,), bool)


def _fault_none_step(alive, key, cfg: SwarmConfig):
    del key
    return alive


def _fault_markov_init(key, cfg: SwarmConfig, n: int):
    # start at the chain's stationary distribution so short runs see churn
    p_down = cfg.fault_mean_down_s / (cfg.fault_mean_up_s
                                      + cfg.fault_mean_down_s)
    return ~jax.random.bernoulli(key, p_down, (n,))


def _fault_markov_step(alive, key, cfg: SwarmConfig):
    dt = cfg.decision_period_s
    p_fail = 1.0 - jnp.exp(-dt / cfg.fault_mean_up_s)
    p_recover = 1.0 - jnp.exp(-dt / cfg.fault_mean_down_s)
    u = jax.random.uniform(key, alive.shape)
    return jnp.where(alive, u >= p_fail, u < p_recover)


# ---------------------------------------------------------------------------
# workload: Markov-modulated (bursty) arrivals — part of the scenario
# ---------------------------------------------------------------------------


def burst_arrivals(burst_on, key, cfg: SwarmConfig):
    """One tick of the per-node ON/OFF arrival chain (Fig. 1 workload).

    Long-run mean inter-arrival stays ``task_period_s``; while ON, tasks
    arrive at rate 1/(period·duty).  Returns (burst_on', arrive [N] bool).
    """
    tick = cfg.tick_s
    k_sw, k_ar = jax.random.split(key)
    duty = cfg.burst_on_s / (cfg.burst_on_s + cfg.burst_off_s)
    p_on_off = 1.0 - jnp.exp(-tick / cfg.burst_on_s)
    p_off_on = 1.0 - jnp.exp(-tick / cfg.burst_off_s)
    flip = jax.random.uniform(k_sw, burst_on.shape)
    burst_on = jnp.where(burst_on, flip >= p_on_off, flip < p_off_on)
    p_arr = 1.0 - jnp.exp(-tick / (cfg.task_period_s * duty))
    arrive = jax.random.bernoulli(k_ar, p_arr, burst_on.shape) & burst_on
    return burst_on, arrive


# ---------------------------------------------------------------------------
# built-in registrations
# ---------------------------------------------------------------------------

register_mobility("circular", _mobility.init_mobility,
                  _mobility.step_circular)
register_mobility("random_waypoint", _mobility.init_random_waypoint,
                  _mobility.step_random_waypoint)
register_mobility("gauss_markov", _mobility.init_gauss_markov,
                  _mobility.step_gauss_markov)
register_mobility("levy_flight", _mobility.init_levy_flight,
                  _mobility.step_levy_flight)

register_channel("two_ray", _channel.two_ray)
register_channel("free_space", _channel.free_space)
register_channel("log_normal", _channel.log_normal)
register_channel("log_normal_corr", _channel.log_normal_corr)
register_channel("rician", _channel.rician)
register_channel("nakagami", _channel.nakagami)

# sparse per-edge twins (no log_normal_corr: see get_channel_edges)
register_channel_edges("two_ray", _channel.two_ray_edges)
register_channel_edges("free_space", _channel.free_space_edges)
register_channel_edges("log_normal", _channel.log_normal_edges)
register_channel_edges("rician", _channel.rician_edges)
register_channel_edges("nakagami", _channel.nakagami_edges)

register_fault("none", _fault_none_init, _fault_none_step)
register_fault("markov", _fault_markov_init, _fault_markov_step)
