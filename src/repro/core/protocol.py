"""Algorithm 1 — the per-epoch decision logic, as one pure JAX function.

``decision_epoch`` is the one composition of the protocol (lines 2-11):
the φ update (Eq. 10, kernel-dispatched), the transfer decision (Eqs.
11-13) and the congestion-aware early exit (Eqs. 14-16).  The swarm
simulator takes its φ, its Distributed decision and its exit labels from
it, over either neighbourhood format (``core.neighborhood``).  It consumes
only one-hop-visible state (M_i(t), neighbor φ/U) — the vectorized form
computes all nodes' decisions at once but never reads beyond M_i(t).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.decision import TransferDecision, transfer_decision
from repro.core.diffusive import phi_update_op
from repro.core.early_exit import (CongestionState, congestion_update,
                                   exit_boundary_layers, exit_label)
from repro.core.neighborhood import Neighborhood
from repro.obs.scopes import phase


class ProtocolState(NamedTuple):
    phi: jax.Array               # [N] aggregated computation capability
    congestion: CongestionState  # (prev_T, D) per node


class EpochDecision(NamedTuple):
    decision: TransferDecision   # utilization / target / transfer per node
    exit_layers: jax.Array       # [N] layers to execute this epoch (Eq. 16)
    exit_lbl: jax.Array          # [N] 0=full 1=medium 2=high congestion
    state: ProtocolState


def init_protocol(F: jax.Array) -> ProtocolState:
    n = F.shape[0]
    return ProtocolState(
        phi=F,
        congestion=CongestionState(jnp.zeros((n,), jnp.float32),
                                   jnp.zeros((n,), jnp.float32)))


def decision_epoch(state: ProtocolState, *, F, nbhd: Neighborhood, d_tx,
                   queued_gflops, gamma: float, dt: float, alpha: float,
                   tau_med: float, tau_high: float,
                   exit_points: Tuple[int, int, int],
                   finalize_layers: int,
                   early_exit_enabled: bool = True) -> EpochDecision:
    """One decision epoch at every node (Alg. 1 lines 2-11), vectorized.

    F [N] GFLOP/s, ``nbhd`` the one-hop neighbourhoods (mask [N, M]), d_tx
    [N, M] s/GFLOP, queued_gflops [N].
    """
    # line 2: update aggregated capability (Eq. 10)
    phi = phi_update_op(state.phi, F, nbhd, d_tx)
    # lines 3-5: utilization, least-utilized neighbor, offload predicate
    dec = transfer_decision(queued_gflops, phi, nbhd, gamma)
    # lines 10-11: congestion indicator + exit label
    with phase("early_exit"):
        cong = congestion_update(state.congestion, queued_gflops, dt, alpha)
        if early_exit_enabled:
            lbl = exit_label(cong.D, tau_med, tau_high)
        else:
            lbl = jnp.zeros(cong.D.shape, jnp.int32)
        layers = exit_boundary_layers(lbl, exit_points, finalize_layers)
    return EpochDecision(dec, layers, lbl, ProtocolState(phi, cong))
