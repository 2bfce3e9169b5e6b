"""Aggregated computation capability — the paper's diffusive metric (Eq. 10).

    1/φ_i(t+1) = 1/(|M_i(t)|+1) · ( 1/F_i + max_{k∈M_i(t)} ( d^tx_{i,k}(t) + 1/φ_k(t) ) )

φ is an effective processing rate (GFLOP/s) under even one-hop load
sharing; the max term is the slowest collaborator.  Fully distributed in the
protocol sense (one-hop state only); vectorized here as a masked max-plus
row reduction over the neighbourhood, a dense [N, N] adjacency or [N, K]
neighbour lists (DESIGN.md §2, §11) — the Pallas ``diffusive_phi`` kernels
implement the same contraction with VMEM tiling.

All functions are pure jnp: they vmap over Monte-Carlo runs and scan over
decision epochs inside the swarm simulator.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.neighborhood import Neighborhood
from repro.obs.scopes import phase

NEG = -1e30


def neighbor_mask(snr_db: jax.Array, snr_min_db: float) -> jax.Array:
    """Eq. 9: M_i(t) = { j != i : SNR_ij >= SNR_min }.  snr_db [N, N]."""
    n = snr_db.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    return (snr_db >= snr_min_db) & ~eye


def phi_update(phi: jax.Array, F: jax.Array, adj: jax.Array,
               d_tx: jax.Array) -> jax.Array:
    """One synchronous iteration of Eq. 10.

    phi [N] current aggregated capability (GFLOP/s), F [N] local capability,
    adj [N, N] boolean one-hop adjacency, d_tx [N, N] per-unit-workload
    transfer delay (s/GFLOP).  Returns phi' [N].

    Isolated nodes (|M_i| = 0) fall back to φ_i = F_i.
    """
    with phase("phi_update"):
        inv_phi = 1.0 / phi                                 # [N] s/GFLOP
        # worst collaborator: max_k ( d_tx[i,k] + 1/phi_k ) over neighbors
        cand = jnp.where(adj, d_tx + inv_phi[None, :], NEG)  # [N, N]
        worst = jnp.max(cand, axis=1)                       # [N]
        deg = jnp.sum(adj, axis=1)                          # [N]
        inv_new = (1.0 / F + worst) / (deg + 1.0)
        phi_new = 1.0 / inv_new
        return jnp.where(deg > 0, phi_new, F)


def phi_update_op(phi: jax.Array, F: jax.Array, nbhd: Neighborhood,
                  d_tx: jax.Array) -> jax.Array:
    """Backend-dispatched Eq. 10 (the simulator hot path).

    Routes the masked max-plus reduction through ``kernels.ops`` —
    ``diffusive_phi`` over a dense ``[N, N]`` neighbourhood,
    ``diffusive_phi_sparse`` (a gather-max) over ``[N, K]`` lists: the
    tiled Pallas kernels on TPU (or in interpret mode under
    ``REPRO_FORCE_INTERPRET=1``), the jnp references elsewhere.  Accepts
    [N] or batched [R, N] operands (``d_tx`` shaped like ``nbhd.mask``);
    the isolated-node fallback (φ_i = F_i exactly) is applied here so
    results match ``phi_update`` / ``phi_update_sparse`` to float32
    rounding.
    """
    from repro.kernels import ops  # deferred: keep core import-light

    with phase("phi_update"):
        inv_phi = 1.0 / phi
        dtx_m = jnp.where(nbhd.mask, d_tx, NEG)
        if nbhd.ids is None:
            kernel, ids = ops.diffusive_phi, ()
        else:
            kernel, ids = ops.diffusive_phi_sparse, (nbhd.ids,)
        if inv_phi.ndim == 1:
            inv_new = kernel(inv_phi[None], F[None], dtx_m[None],
                             *(x[None] for x in ids))[0]
        else:
            inv_new = kernel(inv_phi, F, dtx_m, *ids)
        deg = jnp.sum(nbhd.mask, axis=-1)
        return jnp.where(deg > 0, 1.0 / inv_new, F)


def phi_update_sparse(phi: jax.Array, F: jax.Array, adj_e: jax.Array,
                      nbr: jax.Array, d_tx_e: jax.Array) -> jax.Array:
    """Eq. 10 over fixed-width neighbor lists (DESIGN.md §11).

    phi [N], F [N], adj_e [N, K] validity/adjacency of the gathered edges,
    nbr [N, K] neighbor ids, d_tx_e [N, K] per-unit-workload delay on the
    gathered edges.  Bit-identical to ``phi_update`` whenever the lists
    cover every dense neighbor (same candidates, same arithmetic; max is
    order-independent).
    """
    with phase("phi_update"):
        inv_phi = 1.0 / phi
        cand = jnp.where(adj_e, d_tx_e + inv_phi[nbr], NEG)  # [N, K]
        worst = jnp.max(cand, axis=-1)
        deg = jnp.sum(adj_e, axis=-1)
        inv_new = (1.0 / F + worst) / (deg + 1.0)
        return jnp.where(deg > 0, 1.0 / inv_new, F)


def phi_fixpoint(F: jax.Array, adj: jax.Array, d_tx: jax.Array,
                 iters: int = 16, phi0: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Iterate Eq. 10 to (near) fixpoint; returns (phi, residual_history).

    The paper argues geometric convergence (the 1/(|M|+1) factor contracts
    residuals >= 2x per round for |M| >= 1); `residual_history` lets tests
    verify that claim.
    """
    phi = F if phi0 is None else phi0

    def body(phi, _):
        nxt = phi_update(phi, F, adj, d_tx)
        res = jnp.max(jnp.abs(1.0 / nxt - 1.0 / phi))
        return nxt, res

    phi, residuals = jax.lax.scan(body, phi, None, length=iters)
    return phi, residuals


def phi_bounds_ok(phi: jax.Array, F: jax.Array, adj: jax.Array) -> jax.Array:
    """Invariant from the paper's convergence argument: 0 < φ_i <= F_i +
    Σ_{k∈M_i} F_k (nonzero tx delay strictly reduces collaborative rate)."""
    upper = F + adj @ F
    return jnp.all((phi > 0) & (phi <= upper * (1 + 1e-5)))
