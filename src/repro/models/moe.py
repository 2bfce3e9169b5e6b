"""Capacity-based expert-parallel MoE (qwen3-moe / granite-moe).

Design (DESIGN.md §5):
  * experts sharded over the ``'model'`` axis (EP), activations replicated
    over ``'model'`` inside the block; each shard processes only assignments
    whose expert it owns, then a single ``psum('model')`` combines — the same
    collective cost as a TP FFN, with *no dense one-hot dispatch einsums*
    (dispatch is gather/scatter, so HLO FLOPs stay ≈ active FLOPs × capacity
    factor, keeping the roofline useful-FLOP ratio honest).
  * expert weights are additionally FSDP-sharded over the batch axes and
    all-gathered on entry (ZeRO-3 style).
  * per-expert capacity C = ceil(T·k/E · cf); overflow assignments drop
    (Switch-style); slots are filled via an inverse slot→token map so no
    [T·k, d] intermediate is ever materialized.

Works identically without a mesh (single shard, no collectives) — that path
is what the CPU smoke tests exercise.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import dense_init


def init_moe(key, cfg: ModelConfig, dtype):
    m, d = cfg.moe, cfg.d_model
    E, ff = m.num_experts, m.d_ff_expert
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), d, dtype),
        "w_gate": dense_init(ks[1], (E, d, ff), d, dtype),
        "w_up": dense_init(ks[2], (E, d, ff), d, dtype),
        "w_down": dense_init(ks[3], (E, ff, d), ff, dtype),
    }


def specs_moe(cfg: ModelConfig):
    return {
        "router": P(None, None),
        "w_gate": P("model", "data", None),
        "w_up": P("model", "data", None),
        "w_down": P("model", None, "data"),
    }


# ---------------------------------------------------------------------------
# core (single-shard) MoE body
# ---------------------------------------------------------------------------


def _moe_shard(x2d, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
               shard_id, n_shards: int):
    """x2d [T, d] -> ([T, d] local contribution, aux metrics).

    Only assignments owned by this shard's experts contribute; caller psums.
    """
    m = cfg.moe
    T, d = x2d.shape
    E, k = m.num_experts, m.experts_per_token
    E_loc = E // n_shards
    ff = m.d_ff_expert
    cd = x2d.dtype

    # --- routing (computed redundantly on every model shard; T×E is cheap) --
    logits = (x2d @ router_w.astype(cd)).astype(jnp.float32)       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                  # [T, k]
    if m.router_norm_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- flatten assignments --------------------------------------------
    A = T * k
    eid = gate_idx.reshape(A)                                      # [A]
    wgt = gate_vals.reshape(A).astype(jnp.float32)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    lo = shard_id * E_loc
    leid = eid - lo
    mine = (leid >= 0) & (leid < E_loc)
    leid_c = jnp.clip(leid, 0, E_loc - 1)

    # position within expert via cumulative count over [A, E_loc] one-hot
    oh = (mine[:, None] & (leid_c[:, None]
                           == jnp.arange(E_loc, dtype=jnp.int32)[None, :]))
    pos = jnp.take_along_axis(jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1,
                              leid_c[:, None], axis=1)[:, 0]        # [A]

    C = max(1, math.ceil(A / E * m.capacity_factor))
    keep = mine & (pos < C)
    slot = jnp.where(keep, leid_c * C + pos, E_loc * C)             # dummy=last

    # --- inverse maps: slot -> (token, weight, valid) ---------------------
    n_slots = E_loc * C
    slot_tok = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(tok)
    slot_wgt = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(wgt)
    slot_ok = jnp.zeros((n_slots + 1,), jnp.bool_).at[slot].set(True)
    slot_tok, slot_wgt, slot_ok = (slot_tok[:n_slots], slot_wgt[:n_slots],
                                   slot_ok[:n_slots])

    # --- dispatch: gather tokens into [E_loc, C, d] -----------------------
    buf = x2d[slot_tok] * slot_ok[:, None].astype(cd)
    buf = buf.reshape(E_loc, C, d)

    # --- expert FFN (batched over local experts) --------------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(cd)))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(cd))
    y_e = jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(cd))
    y_flat = y_e.reshape(n_slots, d)

    # --- combine: scatter-add weighted expert outputs back to tokens ------
    contrib = (y_flat.astype(jnp.float32)
               * (slot_wgt * slot_ok.astype(jnp.float32))[:, None])
    y = jnp.zeros((T, d), jnp.float32).at[slot_tok].add(
        jnp.where(slot_ok[:, None], contrib, 0.0))

    # --- aux: load-balance loss (Switch eq. 4) + drop fraction ------------
    me = jnp.mean(probs, axis=0)                                    # [E]
    ce = jnp.zeros((E,), jnp.float32).at[eid].add(1.0) / A
    aux = E * jnp.sum(me * ce)
    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) * n_shards / A
    return y.astype(cd), aux, dropped


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def apply_moe(p, cfg: ModelConfig, x, *, mesh=None,
              batch_axes: Tuple[str, ...] = ("data",), model_axis="model",
              fsdp: bool = True):
    """x [B, S, d] -> (y [B, S, d], aux dict).

    fsdp=False (inference weight layout): expert weights enter the shard_map
    replicated across the batch axes — no per-layer ZeRO-3 re-gather, which
    otherwise costs params/16 of link traffic *per decode step* (§Perf).
    """
    B, S, d = x.shape

    if mesh is None or model_axis not in getattr(mesh, "axis_names", ()):
        y, aux, dropped = _moe_shard(
            x.reshape(B * S, d), p["router"], p["w_gate"], p["w_up"],
            p["w_down"], cfg, shard_id=0, n_shards=1)
        return y.reshape(B, S, d), {"moe_aux": aux, "moe_dropped": dropped}

    n_shards = mesh.shape[model_axis]
    bspec = P(batch_axes, None, None)
    fax = batch_axes if fsdp else None

    def body(xb, router_w, w_gate, w_up, w_down):
        sid = jax.lax.axis_index(model_axis)
        if fsdp:
            # ZeRO-3: expert weights FSDP-sharded on d / ff; gather at use.
            w_gate = jax.lax.all_gather(w_gate, batch_axes, axis=1,
                                        tiled=True)
            w_up = jax.lax.all_gather(w_up, batch_axes, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, batch_axes, axis=2,
                                        tiled=True)
        Bl, Sl, dl = xb.shape
        y, aux, dropped = _moe_shard(xb.reshape(Bl * Sl, dl), router_w,
                                     w_gate, w_up, w_down, cfg,
                                     shard_id=sid, n_shards=n_shards)
        y = jax.lax.psum(y, model_axis)
        aux = jax.lax.pmean(aux, model_axis)
        dropped = jax.lax.psum(dropped, model_axis) / n_shards
        return y.reshape(Bl, Sl, dl), aux, dropped

    y, aux, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(None, None), P(model_axis, fax, None),
                  P(model_axis, fax, None),
                  P(model_axis, None, fax)),
        out_specs=(bspec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, {"moe_aux": aux, "moe_dropped": dropped}
