"""Pallas TPU kernel for the diffusive φ update (paper Eq. 10).

The update is a masked max-plus row reduction over the [N, N] link-delay
matrix — at swarm scale (N in the thousands, R Monte-Carlo runs, every
200 ms epoch) this is the protocol's compute hot spot.  Tiling: the delay
matrix streams through VMEM in (BN, BN) tiles; the running row-max and the
degree count live in VMEM scratch across the column grid dimension (TPU
grids execute sequentially, so scratch persists over the reduction dim);
the final combine with 1/F and the degree normalization happens on the last
column tile.  Per-node vectors travel as [R, 1, N] so every block's last
two dimensions are (1, BN): legal under the TPU's (8, 128) tiling for any
run count R.

Grid: (R, N/BN, N/BN) — Monte-Carlo batch × row tiles × column tiles.

``diffusive_phi_sparse`` is the O(N·K) neighbor-list variant (DESIGN.md
§11).  The TPU kernel language has no vector gather, so XLA gathers each
slot's neighbor 1/φ into a dense [R, N, K] operand before the call; the
kernel body is then the dense one with a per-slot (not per-column) 1/φ
term.  Invalid slots carry the NEG sentinel and lose the max exactly like
dense off-link columns, so sparse output is bit-identical to dense
whenever K covers the true degree.

Grid: (R, N/BN, K/BK) — Monte-Carlo batch × row tiles × neighbor tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
BN = 128  # tile edge (VPU lane-aligned)


def _kernel(inv_ref, f_ref, dtx_ref, out_ref, acc_ref, deg_ref):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, NEG)
        deg_ref[...] = jnp.zeros_like(deg_ref)

    dtx = dtx_ref[0]                 # [BN, BK]; NEG off-link / invalid
    # + 1/φ_k: a [1, BN] column row (dense) or [BN, BK] per slot (sparse)
    cand = dtx + inv_ref[0]
    acc_ref[...] = jnp.maximum(acc_ref[...], jnp.max(cand, axis=1))
    deg_ref[...] = deg_ref[...] + jnp.sum(
        (dtx > NEG / 2).astype(jnp.float32), axis=1)

    @pl.when(j == nj - 1)
    def _finalize():
        f = f_ref[0, 0]
        deg = deg_ref[...]
        inv_new = (1.0 / f + acc_ref[...]) / (deg + 1.0)
        out_ref[0, 0] = jnp.where(deg > 0, inv_new, 1.0 / f)


def _phi_call(inv, F, dtx, inv_spec, bk, interpret):
    """inv (block ``inv_spec``), F [R, Np], dtx [R, Np, C] -> [R, Np]."""
    R, Np, C = dtx.shape
    vec = pl.BlockSpec((1, 1, BN), lambda r, i, j: (r, 0, i))   # F, out rows
    out = pl.pallas_call(
        _kernel,
        grid=(R, Np // BN, C // bk),
        in_specs=[inv_spec, vec,
                  pl.BlockSpec((1, BN, bk), lambda r, i, j: (r, i, j))],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((R, 1, Np), F.dtype),
        scratch_shapes=[pltpu.VMEM((BN,), jnp.float32),
                        pltpu.VMEM((BN,), jnp.float32)],
        interpret=interpret,
    )(inv, F[:, None, :], dtx)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def diffusive_phi(inv_phi, F, d_tx_masked, *, interpret=False):
    """inv_phi [R, N] (s/GFLOP), F [R, N], d_tx_masked [R, N, N] (-inf
    off-link) -> inv_phi' [R, N].  Pads N to a BN multiple internally;
    padding columns are off-link so they never win the max."""
    N = inv_phi.shape[1]
    Np = (N + BN - 1) // BN * BN
    inv_phi = jnp.pad(inv_phi, ((0, 0), (0, Np - N)), constant_values=1.0)
    F = jnp.pad(F, ((0, 0), (0, Np - N)), constant_values=1.0)
    d_tx_masked = jnp.pad(d_tx_masked, ((0, 0), (0, Np - N), (0, Np - N)),
                          constant_values=NEG)
    cols = pl.BlockSpec((1, 1, BN), lambda r, i, j: (r, 0, j))  # 1/φ (cols)
    return _phi_call(inv_phi[:, None, :], F, d_tx_masked, cols, BN,
                     interpret)[:, :N]


BK = 128  # neighbor-tile width (lane-aligned) once K exceeds one tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def diffusive_phi_sparse(inv_phi, F, d_tx_masked, nbr, *, interpret=False):
    """inv_phi [R, N] (s/GFLOP), F [R, N], d_tx_masked [R, N, K] (NEG on
    invalid/off-link slots), nbr [R, N, K] int32 -> inv_phi' [R, N].

    Pads N to a BN multiple and, when K spans several tiles, K to a BK
    multiple; pad slots carry the NEG sentinel so they never win the max
    or count toward the degree.  A single tile spans K itself (a block
    dimension equal to the array's is always legal), so small K is not
    padded to the lane width.
    """
    R, N, K = d_tx_masked.shape
    Np = (N + BN - 1) // BN * BN
    bk = K if K <= BK else BK
    Kp = (K + bk - 1) // bk * bk
    inv_nbr = jax.vmap(lambda v, idx: v[idx])(inv_phi, nbr)   # [R, N, K]
    F = jnp.pad(F, ((0, 0), (0, Np - N)), constant_values=1.0)
    pad = ((0, 0), (0, Np - N), (0, Kp - K))
    d_tx_masked = jnp.pad(d_tx_masked, pad, constant_values=NEG)
    inv_nbr = jnp.pad(inv_nbr, pad, constant_values=0.0)
    slots = pl.BlockSpec((1, BN, bk), lambda r, i, j: (r, i, j))
    return _phi_call(inv_nbr, F, d_tx_masked, slots, bk, interpret)[:, :N]
