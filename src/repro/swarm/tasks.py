"""ML task model (paper §3.1 + Fig. 1 lower panel).

A task is an L-layer sequential DAG (vertical split points at every layer
boundary).  The illustrative profile is a detection-CNN shape: GFLOPs
front-loaded, activation sizes decaying from feature-map scale to
vector scale.  Exit points at [15, 30, 60] with +3 finalize layers
(Table 2).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SwarmConfig


class TaskProfile(NamedTuple):
    gflops: jax.Array        # [L] per-layer GFLOPs
    cum_gflops: jax.Array    # [L+1] cumulative (cum[0] = 0)
    act_bits: jax.Array      # [L+1] activation size crossing boundary l
                             # (act_bits[0] = raw input)
    bits_per_gflop: float    # mean activation bits per GFLOP (for d_tx)
    total_gflops: float


def make_profile(cfg: SwarmConfig) -> TaskProfile:
    L = cfg.task_layers
    # GFLOPs: linear decay 2 -> 0.5 (conv backbone heavier than head)
    w = np.linspace(2.0, 0.5, L)
    g = w / w.sum() * cfg.task_gflops_total
    cum = np.concatenate([[0.0], np.cumsum(g)])
    # activations: raw input ~0.5 MB; feature maps decay 2 MB -> 64 KB
    act_bytes = np.concatenate([
        [0.5e6], np.geomspace(2.0e6, 64e3, L)])
    act_bits = act_bytes * 8.0
    bits_per_gflop = float(act_bits[1:].mean()) / float(g.mean())
    return TaskProfile(
        gflops=jnp.asarray(g, jnp.float32),
        cum_gflops=jnp.asarray(cum, jnp.float32),
        act_bits=jnp.asarray(act_bits, jnp.float32),
        bits_per_gflop=bits_per_gflop,
        total_gflops=float(cfg.task_gflops_total),
    )


def layer_of(profile: TaskProfile, cum_done: jax.Array) -> jax.Array:
    """Last *completed* layer boundary for a progress value (partial layer
    work does not count — §3.1 discard-on-offload)."""
    # oob: searchsorted's own CLIP gathers (inside jax) read in-range
    return jnp.searchsorted(profile.cum_gflops, cum_done, side="right") - 1


def boundary_bits(profile: TaskProfile, cum_done: jax.Array) -> jax.Array:
    """Bits that must be shipped when offloading at the current boundary."""
    lyr = jnp.clip(layer_of(profile, cum_done), 0, profile.act_bits.shape[0] - 1)
    return profile.act_bits[lyr]


def snap_to_boundary(profile: TaskProfile, cum_done: jax.Array) -> jax.Array:
    """Discard partial-layer progress (§3.1)."""
    lyr = jnp.clip(layer_of(profile, cum_done), 0,
                   profile.cum_gflops.shape[0] - 1)
    return profile.cum_gflops[lyr]
