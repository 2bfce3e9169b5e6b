"""Fixed-width neighbor lists via spatial-hash bucket search (DESIGN.md §11).

The diffusive protocol (Eq. 10) is strictly one-hop-local, yet the dense
hot path materializes [N, N] distance/gain/capacity matrices every epoch.
This module builds the sparse alternative: per-node top-k nearest-neighbor
index lists ``nbr [N, K]`` (+ validity mask) from positions, in O(N) per
epoch at fixed K:

  1. hash every node into a ``G × G`` grid of cells (cell edge ≈ the
     channel's communication range, capped by a density heuristic so the
     candidate set stays ~K-sized even when the radio range spans the
     whole mission area);
  2. sort node ids by cell id once — ``searchsorted`` then yields each
     cell's contiguous [start, end) slice, i.e. a bucket table without any
     variable-width structure;
  3. every node gathers a fixed window of ``cap`` candidates from each of
     its 9 surrounding cells (out-of-grid offsets masked, never wrapped,
     so no candidate appears twice) and keeps the K nearest by squared
     distance (``lax.top_k``).

All shapes are static under jit (grid size, cell capacity and K are
derived from the config in Python), so the builder scans/vmaps exactly
like the rest of the simulator.  Exactness: if every true neighbor lies
within one cell edge (cell ≥ comm range), no cell overflows ``cap``, and
K ≥ the true max degree, the K-nearest lists contain *exactly* the dense
adjacency's neighbor sets — the regime the sparse-vs-dense parity tests
pin.  Beyond it (huge N, K ≪ degree) the lists are the K nearest
candidates: the truncated-degree approximation DESIGN.md §11 discusses.

Lists are canonicalized to ascending node id (invalid slots pushed to the
end) so downstream argmin/argmax tie-breaks match the dense path's
lowest-index-wins convention bit-for-bit.

``epoch_links`` is the one reader of ``cfg.neighbor_mode``: it builds an
epoch's links as a ``core.neighborhood.Neighborhood`` in either format,
so the decisions downstream never ask which one they hold.
"""
from __future__ import annotations

import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SwarmConfig
from repro.core.neighborhood import Neighborhood
from repro.obs.scopes import phase
from repro.swarm.channel import edge_rate, link_state, link_state_sparse
from repro.swarm.scenario import get_channel, get_channel_edges

# grid resolution cap: G² cells must stay cheap to searchsorted over
MAX_GRID = 256


def comm_range_m(cfg: SwarmConfig) -> float:
    """Distance at which the selected channel's *deterministic* pathloss
    baseline crosses ``snr_min_db`` (the Eq. 9 adjacency threshold).

    Stochastic models get a fade margin on top (3σ shadowing, ~10 dB for
    the unit-mean fading envelopes) so candidates that only connect on a
    lucky draw still enter the search window.  Unknown (user-registered)
    channels fall back to the mission-area diagonal — conservative; set
    ``cfg.neighbor_range_m`` to override.
    """
    if cfg.neighbor_range_m > 0.0:
        return cfg.neighbor_range_m
    diag = cfg.area_m * math.sqrt(2.0)
    budget = cfg.tx_power_dbm - cfg.noise_dbm - cfg.snr_min_db
    name = cfg.channel_model
    if name == "two_ray":
        r = 10.0 ** ((budget
                      + 20.0 * math.log10(cfg.altitude_m * cfg.altitude_m))
                     / 40.0)
    elif name in ("free_space", "log_normal", "log_normal_corr", "rician",
                  "nakagami"):
        fspl1 = 20.0 * math.log10(cfg.carrier_hz) - 147.55
        n_exp = 2.0 if name == "free_space" else cfg.pathloss_exp
        margin = 0.0
        if name in ("log_normal", "log_normal_corr"):
            margin = 3.0 * cfg.shadowing_sigma_db
        elif name in ("rician", "nakagami"):
            margin = 10.0
        r = 10.0 ** ((budget - fspl1 + margin) / (10.0 * n_exp))
    else:
        r = diag
    return min(r, diag)


def grid_geometry(cfg: SwarmConfig, n: int, k: int) -> Tuple[int, float, int]:
    """Static (G, cell_m, cell_cap) of the bucket grid for an N-node swarm.

    The cell edge is the smaller of the channel range (exact coverage when
    it fits) and a density heuristic sized so the 3×3 search window holds
    a few K's worth of candidates (the complete-graph regime, where range
    covers the whole area and exact coverage would degenerate to O(N²)).
    All three outputs are Python scalars — static under jit.
    """
    r = comm_range_m(cfg)
    density_cell = 0.75 * cfg.area_m * math.sqrt(max(k, 1) / max(n, 1))
    target = max(min(r, density_cell), cfg.area_m / MAX_GRID)
    # floor, not ceil: the realized cell = area/G must stay >= target, so
    # that when the range is the binding constraint (cell >= r) the 3x3
    # window provably covers every in-range neighbor
    G = max(int(cfg.area_m / target), 1)
    cell = cfg.area_m / G
    if cfg.neighbor_cell_cap > 0:
        cap = cfg.neighbor_cell_cap
    elif n <= 1024:
        cap = n          # small swarms: exact, 9n candidates are cheap
    else:
        lam = n / float(G * G)       # mean cell occupancy
        cap = max(2 * k, int(math.ceil(4.0 * lam)) + 8)
    return G, cell, min(cap, n)


def neighbor_lists(pos: jax.Array, cfg: SwarmConfig, k: int | None = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """pos [N, 2] → (nbr [N, K] int32 ascending by id, valid [N, K] bool).

    Valid slots hold the K nearest distinct nodes within the candidate
    radius; invalid slots carry index 0 and are masked everywhere
    downstream (the NEG off-link convention of the φ kernels).
    """
    n = pos.shape[0]
    k = cfg.neighbor_k if k is None else k
    k = max(1, min(k, n - 1)) if n > 1 else 1
    G, cell, cap = grid_geometry(cfg, n, k)
    r = comm_range_m(cfg)

    ix = jnp.clip((pos[:, 0] / cell).astype(jnp.int32), 0, G - 1)
    iy = jnp.clip((pos[:, 1] / cell).astype(jnp.int32), 0, G - 1)
    cid = ix * G + iy
    order = jnp.argsort(cid)                       # node ids sorted by cell
    scid = cid[order]
    cells = jnp.arange(G * G, dtype=cid.dtype)
    # oob: searchsorted's own CLIP gathers (inside jax) read in-range
    starts = jnp.searchsorted(scid, cells)
    ends = jnp.searchsorted(scid, cells, side="right")

    window = jnp.arange(cap)
    cand_parts, ok_parts = [], []
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            cx, cy = ix + dx, iy + dy
            in_grid = (cx >= 0) & (cx < G) & (cy >= 0) & (cy < G)
            c = jnp.clip(cx, 0, G - 1) * G + jnp.clip(cy, 0, G - 1)
            s, e = starts[c], ends[c]              # [N] bucket slices
            slot = s[:, None] + window[None, :]    # [N, cap]
            ok = in_grid[:, None] & (slot < e[:, None])
            cand_parts.append(order[jnp.clip(slot, 0, n - 1)])
            ok_parts.append(ok)
    cand = jnp.concatenate(cand_parts, axis=1)     # [N, 9·cap]
    ok = jnp.concatenate(ok_parts, axis=1)

    d2 = jnp.sum(jnp.square(pos[:, None, :] - pos[cand]), axis=-1)
    ok &= cand != jnp.arange(n)[:, None]           # never your own neighbor
    ok &= d2 <= jnp.float32(r * r)                 # candidate-radius cut
    score = jnp.where(ok, d2, jnp.inf)
    neg_d2, sel = jax.lax.top_k(-score, k)         # k smallest distances
    # oob: `sel` comes from top_k over the candidate axis, always
    # in-range; fill mode is take_along_axis's default (J003)
    nbr = jnp.take_along_axis(cand, sel, axis=1)
    valid = neg_d2 > -jnp.inf
    # canonical ascending-id order (invalid slots last): argmin/argmax
    # tie-breaks over the K axis then match dense lowest-index-wins
    key = jnp.where(valid, nbr, n)
    perm = jnp.argsort(key, axis=1)
    # oob: `perm` is an argsort permutation, in-range by construction
    nbr = jnp.take_along_axis(nbr, perm, axis=1)
    valid = jnp.take_along_axis(valid, perm, axis=1)
    return jnp.where(valid, nbr, 0).astype(jnp.int32), valid


def epoch_links(pos: jax.Array, alive: jax.Array, cfg: SwarmConfig, key
                ) -> Tuple[Neighborhood, jax.Array, Callable]:
    """One epoch's links in the representation ``cfg.neighbor_mode``
    selects (read here only): ``(nbhd, cap, rate_to)``.

    * ``nbhd``: the alive-masked one-hop neighbourhoods (Eq. 9), dense
      ``[N, N]`` or ``[N, K]`` lists from ``neighbor_lists``;
    * ``cap`` ``[N, M]``: the link capacity in bit/s of each slot
      (``channel.link_state`` / ``link_state_sparse``);
    * ``rate_to(dst)``: each node's ``[N]`` rate toward ``dst`` —
      ``cap[rows, dst]`` dense, ``channel.edge_rate`` for lists, on the
      same epoch ``key`` so its draws are the ones the decision saw.
    """
    if cfg.neighbor_mode == "sparse":
        edge_fn = get_channel_edges(cfg)
        with phase("neighbors"):
            ids, valid = neighbor_lists(pos, cfg)
            nbhd = Neighborhood(valid, ids).masked(alive)
        with phase("channel"):
            adj, cap = link_state_sparse(pos, ids, nbhd.mask, cfg, key=key,
                                         pathloss_fn=edge_fn)
        return (nbhd._replace(mask=adj), cap,
                lambda dst: edge_rate(pos, dst, cfg, key=key,
                                      pathloss_fn=edge_fn))
    with phase("channel"):
        adj, cap = link_state(pos, cfg, key=key, pathloss_fn=get_channel(cfg))
        nbhd = Neighborhood(adj).masked(alive)
    # i32 rows: default arange is i64 under x64 (swarmlint J002)
    rows = jnp.arange(pos.shape[0], dtype=jnp.int32)
    return nbhd, cap, lambda dst: cap[rows, dst]
