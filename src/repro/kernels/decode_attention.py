"""Pallas TPU flash-decode: one new query against a long KV cache.

Grid (B, Hkv, Sk/BK): the KV sequence streams through VMEM in (BK, hd)
tiles while the G = Hq/Hkv query heads for this kv-head stay resident
([G, hd], G ≤ 32 → a few KB).  Online softmax accumulators in VMEM scratch
across the (sequential) key grid dimension.  Position masking supports the
paper-relevant cases: plain causal (k ≤ pos), sliding window, and the
hybrid model's ring-buffer caches (negative positions = unwritten slots).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
DEFAULT_BK = 512


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, window, bk):
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)          # [BK, hd]
    v = v_ref[0, 0].astype(jnp.float32)          # [BK, hd]
    pos = pos_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)[0]
    keep = kpos <= pos
    if window > 0:
        keep &= (pos - kpos) < window
    s = jnp.where(keep[None, :], s, NEG)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q, k, v, pos, *, window=0, bk=DEFAULT_BK,
                     interpret=False):
    """q [B,Hq,hd]; k/v [B,S,Hkv,hd]; pos [] int32 -> [B,Hq,hd]."""
    B, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    scale = 1.0 / math.sqrt(hd)

    qt = q.reshape(B, Hkv, G, hd)
    kt = jnp.swapaxes(k, 1, 2)                   # [B, Hkv, S, hd]
    vt = jnp.swapaxes(v, 1, 2)
    pos_arr = jnp.reshape(jnp.asarray(pos, jnp.int32), (1,))

    grid = (B, Hkv, S // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, bk=bk),
        grid=grid,
        in_specs=[
            # the whole (1,) array in SMEM: a rank-1 block must span the
            # array or a multiple of 128 lanes
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G, hd), jnp.float32)],
        interpret=interpret,
    )(pos_arr, qt, kt, vt)
    return out.reshape(B, Hq, hd)
