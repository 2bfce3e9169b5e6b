"""Attention: GQA with qk-norm / bias / sliding window.

Two execution paths:
  * ``chunked_attention`` — q-chunked, ``lax.scan`` over chunks; peak live
    score tensor is [B, Hkv, G, chunk, S_kv] instead of [B, H, S, S].  This is
    what the multi-pod dry-run lowers (prefill_32k would otherwise claim a
    TB-scale buffer).  On TPU the Pallas ``flash_attention`` kernel replaces it
    (``repro.kernels.ops`` dispatch).
  * ``decode_attention_ref`` — single-query attention over a KV cache, exact
    row softmax; KV cache sequence dim is sharded over ``'model'`` so XLA
    partitions the softmax reductions into partial-max/partial-sum
    all-reduces (distributed flash-decode).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import (apply_rope, dense_init, rms_head_norm,
                                 rope_angles)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.head_dim_
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, Hq, hd), d, dtype),
        "wk": dense_init(ks[1], (d, Hkv, hd), d, dtype),
        "wv": dense_init(ks[2], (d, Hkv, hd), d, dtype),
        "wo": dense_init(ks[3], (Hq, hd, d), Hq * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq, hd), dtype)
        p["bk"] = jnp.zeros((Hkv, hd), dtype)
        p["bv"] = jnp.zeros((Hkv, hd), dtype)
    if cfg.attn_out_bias:
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def specs_attention(cfg: ModelConfig):
    # q heads sharded over 'model' (padded when H % shards != 0); kv heads are
    # few (1..16) => replicated over 'model'; all weights FSDP over 'data'.
    s = {
        "wq": P("data", "model", None),
        "wk": P("data", None, None),
        "wv": P("data", None, None),
        "wo": P("model", None, "data"),
    }
    if cfg.qkv_bias:
        s.update({"bq": P("model", None), "bk": P(None, None),
                  "bv": P(None, None)})
    if cfg.attn_out_bias:
        s["bo"] = P(None)
    if cfg.qk_norm:
        s.update({"q_norm": P(None), "k_norm": P(None)})
    return s


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------


def qkv_project(p, cfg: ModelConfig, x, positions, *, rope=True):
    """x [B,S,d] -> q [B,S,Hq,hd], k,v [B,S,Hkv,hd] (rope applied)."""
    cd = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if rope:
        cos, sin = rope_angles(positions, cfg.head_dim_, cfg.rope_theta,
                               cfg.mrope_sections)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def out_project(p, cfg: ModelConfig, o):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if cfg.attn_out_bias:
        y = y + p["bo"].astype(o.dtype)
    return y


# ---------------------------------------------------------------------------
# chunked attention (train / prefill reference path)
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """[..., Cq, Sk] additive bias from causal/window constraints."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    keep = jnp.ones_like(diff, dtype=bool)
    if causal:
        keep &= diff >= 0
    if window and window > 0:
        keep &= diff < window
    return jnp.where(keep, 0.0, NEG_INF)


def chunked_attention(q, k, v, *, q_positions, k_positions, causal=True,
                      window: int = 0, chunk: int = 1024,
                      standard_layout: bool = True,
                      unroll: bool = False) -> jax.Array:
    """q [B,Sq,Hq,hd], k/v [B,Sk,Hkv,hd] -> [B,Sq,Hq,hd].

    lax.scan over q chunks; per-chunk full-row scores (fp32 softmax).
    On TPU (and under REPRO_FORCE_INTERPRET) dispatches to the Pallas
    flash-attention kernel when positions are the standard arange layout
    and both sequence lengths are multiples of 128 (the kernel's 128-row
    tiles); any other length runs this reference path, on the chip too.
    ``chip_smoke.py`` asserts that its serving run reaches the kernel.
    """
    if standard_layout:
        from repro.kernels import ops as kops
        if kops._mode() != "ref" and q.shape[1] % 128 == 0 \
                and k.shape[1] % 128 == 0:
            return kops.flash_attention(q, k, v, causal=causal,
                                        window=window)
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    chunk = min(chunk, Sq)
    if Sq % chunk != 0:   # smoke-sized inputs: single chunk
        chunk = Sq
    nq = Sq // chunk

    qg = q.reshape(B, nq, chunk, Hkv, G, hd)
    qg = jnp.moveaxis(qg, 1, 0)                       # [nq,B,C,Hkv,G,hd]
    qpos = jnp.moveaxis(q_positions.reshape(B, nq, chunk), 1, 0)

    def one_chunk(_, xs):
        qc, qp = xs                                   # [B,C,Hkv,G,hd], [B,C]
        s = jnp.einsum("bckgd,bskd->bkgcs", qc, k).astype(jnp.float32) * scale
        bias = _mask_bias(qp[:, None, None, :], k_positions[:, None, None, :],
                          causal, window)             # [B,1,1,C,Sk]
        s = s + bias
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jax.lax.stop_gradient(m))
        z = jnp.sum(e, axis=-1, keepdims=True)
        pattn = (e / z).astype(v.dtype)
        o = jnp.einsum("bkgcs,bskd->bckgd", pattn, v)
        return None, o

    if unroll:   # exact HLO cost accounting for the dry-run (DESIGN.md §6)
        from repro.models.common import unrolled_scan
        _, os = unrolled_scan(one_chunk, None, (qg, qpos))
    else:
        _, os = jax.lax.scan(one_chunk, None, (qg, qpos))
    o = jnp.moveaxis(os, 0, 1).reshape(B, Sq, Hq, hd)
    return o


# ---------------------------------------------------------------------------
# decode attention (single new token vs. KV cache)
# ---------------------------------------------------------------------------


def decode_attention_ref(q, k_cache, v_cache, *, q_position, k_positions,
                         window: int = 0,
                         standard_layout: bool = True) -> jax.Array:
    """q [B,1,Hq,hd]; caches [B,S,Hkv,hd]; attend to k_pos <= q_pos.

    Exact row softmax; with the cache S-dim sharded over 'model', XLA emits
    partial max/sum all-reduces (distributed flash-decode).  On TPU,
    arange-layout caches dispatch to the Pallas flash-decode kernel
    (ring-buffer caches — non-monotone k_positions — stay on this path);
    so do caches whose length is not a multiple of 128 (the kernel's key
    tile).
    """
    if standard_layout:
        from repro.kernels import ops as kops
        if kops._mode() != "ref" and k_cache.shape[1] % 128 == 0:
            o = kops.decode_attention(q[:, 0], k_cache, v_cache,
                                      q_position[0], window=window)
            return o[:, None]
    B, _, Hq, hd = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    diff = q_position[:, None] - k_positions[:, :]     # [B,S] (broadcast pos)
    keep = (diff >= 0) & (k_positions >= 0)   # ring-buffer unwritten slots < 0
    if window and window > 0:
        keep &= diff < window
    s = s + jnp.where(keep, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache)
    return o.reshape(B, 1, Hq, hd)
