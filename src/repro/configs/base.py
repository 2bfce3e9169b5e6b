"""Configuration system for the repro framework.

Two config kinds:
  * ModelConfig  — one per assigned architecture (exact public dims).
  * ShapeConfig  — the four assigned input-shape cells.
  * SwarmConfig  — the paper's simulation parameters (Table 2).

All configs are frozen dataclasses; `reduced()` derives the CPU smoke-test
variant of a ModelConfig (same family / same code paths, tiny dims).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    experts_per_token: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router_norm_topk: bool = True     # qwen3-style renormalized top-k gate
    router_aux_loss: float = 0.0      # load-balance aux loss coefficient


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                  # 0 => ceil(d_model / 16)
    chunk: int = 64                   # selective-scan chunk length (train)
    # remat each chunk body: backward saves only the [B, d_in, N] carries
    # instead of the per-chunk [B, chunk, d_in, N] scan states (§Perf lever)
    chunk_remat: bool = False


@dataclass(frozen=True)
class HybridConfig:
    # RecurrentGemma/Griffin-style block pattern, repeated over depth.
    pattern: Tuple[str, ...] = ("rec", "rec", "attn")
    lru_width: int = 0                # 0 => d_model
    conv_width: int = 4
    window: int = 2048                # local-attention window
    # RG-LRU constant `c` (power applied to the recurrence gate).
    c: float = 8.0


@dataclass(frozen=True)
class EncDecConfig:
    encoder_layers: int = 0
    source_positions: int = 1500      # whisper-medium 30 s of audio frames
    max_target_positions: int = 32_768  # learned-pos table size (covers cells)
    # the conv frontend is a stub: input_specs() hands pre-computed frame
    # embeddings of shape [B, source_positions, d_model].


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | hybrid | ssm | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 => d_model // num_heads
    qk_norm: bool = False             # qwen3 per-head RMS norm on q/k
    qkv_bias: bool = False            # qwen2 QKV bias
    attn_out_bias: bool = False
    rope_theta: float = 1_000_000.0
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE (sums to head_dim//2)
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    act: str = "swiglu"               # swiglu | geglu | gelu
    tie_embeddings: bool = False
    learned_pos: bool = False         # whisper: learned absolute positions
    frontend: str = "none"            # none | patch_stub | audio_stub
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    # Early-exit head layers (paper §4.3): indices of layer boundaries at which
    # a truncated inference may produce logits. 0 entries => [L//4, L//2].
    exit_layers: Tuple[int, ...] = ()
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # training-side knobs (hillclimb levers, see EXPERIMENTS.md §Perf)
    remat_policy: str = "nothing"     # nothing | dots | none
    attn_chunk: int = 1024            # q-chunk size for the chunked ref attention
    scan_layers: bool = True
    # cast large (>=1M-element) weight matrices to compute dtype *before*
    # use: the ZeRO-3 all-gathers then move bf16 instead of fp32 (2× less
    # ICI traffic); fp32 master copies stay in the optimizer.
    cast_weights_bf16: bool = False
    # compute lm-head logits + CE in sequence chunks of this size (0 = off):
    # avoids materializing the [B, S, vocab] fp32 logits tensor.
    loss_chunk: int = 0
    # serving (prefill/decode) weight layout: True = ZeRO-3 over the batch
    # axes (min memory, per-step all-gathers); False = weights replicated
    # across the data axis (inference has no optimizer state, so they fit —
    # and the per-step weight gathers disappear).  §Perf lever.
    serve_param_fsdp: bool = True
    # pure data parallelism: batch spans BOTH mesh axes, weights are
    # FSDP-sharded over both, nothing is tensor-parallel.  Exact for
    # attention-free per-channel architectures (mamba): the TP out_proj
    # all-reduces disappear and per-device token count drops by the model-
    # axis width.  §Perf lever (beyond-paper sharding scheme).
    pure_dp: bool = False

    # ---- derived ---------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        if self.num_heads == 0:
            return 0
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def q_groups(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def exit_layers_(self) -> Tuple[int, ...]:
        if self.exit_layers:
            return self.exit_layers
        L = self.num_layers
        return (max(L // 4, 1), max(L // 2, 2))

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_subquadratic(self) -> bool:
        """True if long-context decode (500k) is tractable: SSM state or
        bounded local-attention window instead of a full-length KV cache."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (embeddings included once if tied)."""
        d, hd = self.d_model, self.head_dim_
        Hq, Hkv = self.num_heads, self.num_kv_heads
        attn = d * (Hq * hd) + 2 * d * (Hkv * hd) + (Hq * hd) * d
        if self.qkv_bias:
            attn += (Hq + 2 * Hkv) * hd
        if self.act in ("swiglu", "geglu"):
            mlp = 3 * d * self.d_ff
        else:
            mlp = 2 * d * self.d_ff
        per_layer = 0
        n_attn_layers = self.num_layers
        if self.family == "moe":
            m = self.moe
            moe_mlp = m.num_experts * 3 * d * m.d_ff_expert + d * m.num_experts
            per_layer = attn + moe_mlp + 2 * d
            total = self.num_layers * per_layer
        elif self.family == "ssm":
            s = self.ssm
            d_in = s.expand * d
            dt_rank = s.dt_rank or math.ceil(d / 16)
            blk = (d * 2 * d_in + d_in * s.d_conv
                   + d_in * (dt_rank + 2 * s.d_state) + dt_rank * d_in
                   + d_in * s.d_state + d_in  # A_log, D
                   + d_in * d + d)
            total = self.num_layers * blk
        elif self.family == "hybrid":
            h = self.hybrid
            w = h.lru_width or d
            rec = (2 * d * w + w * h.conv_width + 3 * w  # Λ, gates' diag params
                   + 2 * w * (w // 8)                     # block-diag input gates (a/x)
                   + w * d + 2 * d)
            att = attn + mlp + 2 * d
            n_att = sum(1 for i in range(self.num_layers)
                        if h.pattern[i % len(h.pattern)] == "attn")
            total = n_att * att + (self.num_layers - n_att) * rec
        elif self.family == "encdec":
            e = self.encdec
            enc = e.encoder_layers * (attn + mlp + 2 * d)
            dec = self.num_layers * (2 * attn + mlp + 3 * d)
            total = enc + dec
        else:  # dense / vlm
            total = self.num_layers * (attn + mlp + 2 * d)
        emb = self.vocab_size * d
        total += emb if self.tie_embeddings else 2 * emb
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if self.family != "moe":
            return self.param_count()
        m = self.moe
        d = self.d_model
        dense_moe = self.num_layers * m.num_experts * 3 * d * m.d_ff_expert
        active_moe = self.num_layers * m.experts_per_token * 3 * d * m.d_ff_expert
        return int(self.param_count() - dense_moe + active_moe)


# ---------------------------------------------------------------------------
# Input shapes (assigned cells)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str           # train | prefill | decode
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeConfig("train_4k", "train", 4_096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524_288, 1)

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Assignment skip rules. Returns (runnable, reason-if-skipped)."""
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return False, ("long_500k requires sub-quadratic attention; "
                       f"{cfg.name} is pure full-attention (skip per brief)")
    return True, ""


# ---------------------------------------------------------------------------
# Smoke-test reduction
# ---------------------------------------------------------------------------


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family variant for CPU smoke tests (same code paths)."""
    kw = dict(
        name=cfg.name + "-smoke",
        num_layers=len(cfg.hybrid.pattern) + 2 if cfg.family == "hybrid" else 2,
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) or 1,
        d_ff=128,
        head_dim=16,
        vocab_size=256,
        attn_chunk=32,
        scan_layers=cfg.scan_layers,
    )
    if cfg.mrope_sections:
        kw["mrope_sections"] = (2, 3, 3)   # sums to head_dim//2 = 8
    if cfg.moe:
        # capacity_factor = E guarantees zero drops (worst case: every
        # assignment routes to one expert), making smoke tests exact.
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, experts_per_token=2, d_ff_expert=32,
            capacity_factor=4.0)
    if cfg.ssm:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=4, chunk=8)
    if cfg.hybrid:
        kw["hybrid"] = dataclasses.replace(cfg.hybrid, lru_width=64, window=16)
    if cfg.encdec:
        kw["encdec"] = dataclasses.replace(
            cfg.encdec, encoder_layers=2, source_positions=24)
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# Swarm (paper Table 2)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SwarmConfig:
    num_workers: int = 30
    area_m: float = 20_000.0                 # 20×20 km
    placement_granularity: int = 15
    movement_radius_m: float = 1_000.0
    speed_mps: float = 75.0
    capability_mean: float = 400.0           # GFLOP/s, N(400,100)
    capability_std: float = 100.0
    energy_per_gflop_j: float = 0.02
    task_period_s: float = 0.060             # Markov mean inter-arrival
    # Markov-modulated (bursty) arrivals: per-node ON/OFF chain; long-run
    # mean inter-arrival stays task_period_s, bursts arrive at rate
    # 1/(period*duty) while ON ("event-triggered bursty loads", Fig. 1).
    burst_on_s: float = 2.0                  # mean burst duration
    burst_off_s: float = 6.0                 # mean quiet duration
    exit_points: Tuple[int, int, int] = (15, 30, 60)       # L1, L2, L_full
    exit_finalize_layers: int = 3
    exit_thresholds: Tuple[float, float] = (1.5, 2.5)      # τ_med, τ_high
    exit_accuracy: Tuple[float, float, float] = (0.6, 0.9, 0.95)
    tx_power_dbm: float = 30.0
    noise_dbm: float = -85.0
    snr_min_db: float = 3.0
    bandwidth_hz: float = 10e6
    sim_time_s: float = 100.0
    gamma: float = 0.02                      # distributed offload threshold
    decision_period_s: float = 0.200
    random_offload_p: float = 0.2
    random_acyclic_p: float = 0.1
    greedy_offload_p: float = 0.05
    ema_alpha: float = 0.3                   # smoothing α (Eq. 15)
    # --- simulator discretization (DESIGN.md §3) ---
    tick_s: float = 0.010
    queue_slots: int = 128
    altitude_m: float = 100.0                # two-ray antenna heights
    num_runs: int = 50
    early_exit_enabled: bool = False
    # --- scenario engine (DESIGN.md §3.4): string-keyed model selection ---
    # Every field below is static under jit, so sweeping scenarios is a pure
    # config change — no code edits, one executable per (cfg, n) pair.
    # mobility: circular|random_waypoint|gauss_markov|levy_flight
    mobility_model: str = "circular"
    # channel: two_ray|free_space|log_normal|log_normal_corr|rician|nakagami
    channel_model: str = "two_ray"
    fault_model: str = "none"                # none|markov
    # random-waypoint / Gauss-Markov / Lévy mobility parameters
    speed_min_mps: float = 25.0
    speed_max_mps: float = 100.0
    gm_alpha: float = 0.85                   # Gauss-Markov velocity memory
    gm_sigma_mps: float = 20.0               # Gauss-Markov velocity noise
    levy_alpha: float = 1.6                  # Pareto tail of Lévy hop length
    # free-space / log-normal / fading channel parameters
    carrier_hz: float = 2.4e9
    # log-distance exponent (1 m reference); at the 20 km mission scale,
    # 2.0 keeps a sparse multi-hop topology — exponents > 2.2 disconnect it
    pathloss_exp: float = 2.0
    shadowing_sigma_db: float = 6.0          # log-normal shadowing std
    rician_k_db: float = 6.0                 # Rician K-factor (LoS/NLoS dB)
    nakagami_m: float = 2.0                  # Nakagami shape (1 = Rayleigh)
    # Gudmundson decorrelation distance of the spatially-correlated
    # shadowing model (log_normal_corr): shadowing processes of two nodes
    # d metres apart correlate as exp(-d / shadow_corr_m)
    shadow_corr_m: float = 500.0
    # node fault/churn (markov): mean dwell times of the up/down chain
    fault_mean_up_s: float = 30.0
    fault_mean_down_s: float = 5.0
    # --- neighbor representation (DESIGN.md §11) ---
    # "dense" keeps the historical [N, N] adjacency/capacity hot path
    # (bit-compatible with every earlier PR); "sparse" switches the epoch
    # update to fixed-width [N, K] neighbor lists built by the spatial-hash
    # search in swarm/neighbors.py — per-epoch cost O(N·k) instead of
    # O(N²), exact vs dense whenever neighbor_k covers the true max degree
    # (truncated-degree approximation beyond that).
    neighbor_mode: str = "dense"             # dense|sparse
    neighbor_k: int = 16                     # neighbor-list width K
    # bucket-grid knobs (0 = auto-derived from N, K and the channel range):
    # candidate radius of the grid search in metres, and the fixed per-cell
    # candidate capacity of the sorted-grid buckets
    neighbor_range_m: float = 0.0
    neighbor_cell_cap: int = 0
    # task profile (illustrative detection CNN, DESIGN.md §3)
    task_layers: int = 60
    task_gflops_total: float = 12.0
    # task mix (swarm/tasks.py): the named profiles the swarm's tasks split
    # (cnn60 is the CNN above; vgg16, resnet50 are built from their layer
    # tables) and each one's share of the arrivals.  One profile is the
    # historical program: no per-task profile state exists.
    task_profiles: Tuple[str, ...] = ("cnn60",)
    task_mix: Tuple[float, ...] = (1.0,)
    # --- per-task telemetry (repro.trace, DESIGN.md §10) ---
    # > 0 enables in-scan TaskRecord capture: one fixed-width record per
    # completed/dropped task, scattered by global seq into a buffer of this
    # many slots (records with seq >= capacity are counted as overflow, not
    # captured).  0 (default) is fully off — no trace state exists and
    # every metric is bit-identical to an untraced build.
    trace_capacity: int = 0
    # > 0 enables the second in-scan stream: one fixed-width HopRecord per
    # *delivered transfer* (seq/src/dst/t_depart/t_arrive/bits/
    # boundary_layer/stall_ticks), scattered by a dedicated hop sequence
    # counter assigned at transfer initiation.  Independent of
    # trace_capacity (either stream can be on alone); 0 (default) is fully
    # off with the same zero-cost guarantee.
    trace_hop_capacity: int = 0
    # > 0 enables the third in-scan stream, the swarm-state "flight
    # recorder" (DESIGN.md §12): every trace_state_every-th epoch captures
    # per-node gauges (phi / queue depth / cumulative energy / alive /
    # in-flight bits) plus system aggregates into epoch-indexed buffers of
    # ceil(n_epochs / every) slots.  Memory is O(E/stride · min(N, nodes));
    # 0 (default) is fully off with the same zero-cost guarantee as the
    # task/hop streams.
    trace_state_every: int = 0
    # optional node subsample for the state stream: record gauges only for
    # the first min(N, trace_state_nodes) nodes (deterministic prefix —
    # node identity is arbitrary under i.i.d. placement, so a prefix is an
    # unbiased panel).  System aggregates always span all N nodes.
    # 0 records every node.
    trace_state_nodes: int = 0
