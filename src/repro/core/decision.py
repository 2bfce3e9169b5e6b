"""Task-transfer decision (paper Eqs. 11-13).

    U_i(t)   = T_i(t) / φ_i(t)                         (utilization, Eq. 11)
    k*       = argmin_{k ∈ M_i(t)} U_k(t)              (Eq. 12)
    transfer ⇔ U_i - U_{k*} > γ                        (Eq. 13)

γ is the hysteresis threshold that prevents oscillatory offloading.
Vectorized over all nodes at once.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.neighborhood import Neighborhood

BIG = 1e30


class TransferDecision(NamedTuple):
    utilization: jax.Array   # [N]  U_i
    target: jax.Array        # [N]  k* (argmin-utilization neighbor; -1 if none)
    transfer: jax.Array      # [N]  bool, Eq. 13 predicate


def utilization(queued_gflops: jax.Array, phi: jax.Array) -> jax.Array:
    """Eq. 11. queued_gflops T_i >= 0, phi > 0."""
    return queued_gflops / jnp.maximum(phi, 1e-9)


def transfer_decision(queued_gflops: jax.Array, phi: jax.Array,
                      nbhd: Neighborhood, gamma: float) -> TransferDecision:
    """Eqs. 11-13 for every node simultaneously.

    queued_gflops [N], phi [N], ``nbhd`` the one-hop neighbourhoods.  A
    node with no neighbors never transfers (target = -1).  Neighbour lists
    are sorted ascending by node id, so utilization ties resolve to the
    lowest node id in either format.
    """
    U = utilization(queued_gflops, phi)                   # [N]
    cand = jnp.where(nbhd.mask, nbhd.view(U), BIG)        # [N, M]
    k_star = nbhd.node(jnp.argmin(cand, axis=1))          # [N]
    U_star = jnp.min(cand, axis=1)                        # [N]
    has_nbr = jnp.any(nbhd.mask, axis=1)
    do = has_nbr & ((U - U_star) > gamma)                 # Eq. 13
    return TransferDecision(U, jnp.where(has_nbr, k_star, -1), do)
