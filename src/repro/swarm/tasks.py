"""ML task model (paper §3.1 + Fig. 1 lower panel).

A task is an L-unit sequential DAG (vertical split points at every unit
boundary).  Named profiles (``SwarmConfig.task_profiles``):

* ``cnn60`` — the illustrative detection-CNN shape: GFLOPs front-loaded,
  activation sizes decaying from feature-map scale to vector scale.  Exit
  points at [15, 30, 60] with +3 finalize layers (Table 2).
* ``vgg16`` — VGG-16, configuration D (arXiv:1409.1556, Table 1): the 13
  convolutions, each with the max-pool that follows it merged into it, and
  the 3 fully connected layers; 16 units, 15,470,264,320 MAC.
* ``resnet50`` — ResNet-50 v1 (arXiv:1512.03385, Table 1; the stride on a
  stage's first 1×1): the stem (conv1 + max-pool), the 16 bottleneck
  blocks and the head (avg-pool + fc1000); 18 units, 3,857,973,248 MAC.
  A split inside a block would also ship the skip tensor, so it splits only
  at block boundaries.

Both networks take a 224×224 uint8 RGB input (150,528 B) and pass float32
activations; a unit's GFLOPs are 2 × its MACs.

One profile is a ``TaskProfile`` closed over by the simulator: the tick
loop is the historical one.  Several are a ``ProfileMix``: each task draws
its profile at arrival with the configured shares and carries the id in
its queue slot (``q_profile``) and transfer (``tx_profile``); the tables
are stacked ``[P, Lmax+1]``, padded after each profile's last unit, and
every per-task lookup is a select over the P rows under the
``task_profile`` scope.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SwarmConfig
from repro.obs.scopes import phase

# fold_in constant of the per-arrival profile draw (off the tick key)
PROFILE_KEY = 0x5EED


class TaskProfile(NamedTuple):
    gflops: jax.Array        # [L] per-layer GFLOPs
    cum_gflops: jax.Array    # [L+1] cumulative (cum[0] = 0)
    act_bits: jax.Array      # [L+1] activation size crossing boundary l
                             # (act_bits[0] = raw input)
    bits_per_gflop: float    # mean activation bits per GFLOP (for d_tx)
    total_gflops: float


class ProfileMix(NamedTuple):
    """Several profiles in one swarm, stacked by profile id."""
    names: Tuple[str, ...]
    shares: Tuple[float, ...]
    cum_gflops: np.ndarray           # [P, Lmax+1] f32, +inf past the end
    act_bits: np.ndarray             # [P, Lmax+1] f32, 0 past the end
    done_gflops: np.ndarray          # [P] f32: cum_gflops[p, L_p] (L_p units)
    total_gflops: np.ndarray         # [P] f32 (the load metric's total)
    bits_per_gflop: np.ndarray       # [P] f32
    idle_bits_per_gflop: float       # share-weighted, for an empty queue


def _as_profile(g, cum, act_bits, bits_per_gflop, total) -> TaskProfile:
    return TaskProfile(
        gflops=jnp.asarray(g, jnp.float32),
        cum_gflops=jnp.asarray(cum, jnp.float32),
        act_bits=jnp.asarray(act_bits, jnp.float32),
        bits_per_gflop=bits_per_gflop,
        total_gflops=total,
    )


def _cnn60(cfg: SwarmConfig):
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
    return g, cum, act_bits, bits_per_gflop, float(cfg.task_gflops_total)


INPUT_BYTES = 224 * 224 * 3          # uint8 RGB
ACT_BYTES = 4                        # float32 activations


def vgg16_units():
    """(MACs, output elements) per unit of VGG-16 D: 3×3 convolutions
    (padding 1) in five blocks, each block's 2×2 max-pool merged into its
    last convolution, then fc6, fc7, fc8."""
    units, hw, cin = [], 224, 3
    for block in ((64, 64), (128, 128), (256, 256, 256), (512, 512, 512),
                  (512, 512, 512)):
        for i, cout in enumerate(block):
            mac = hw * hw * cin * cout * 9
            cin = cout
            if i == len(block) - 1:
                hw //= 2
            units.append((mac, hw * hw * cout))
    fin = hw * hw * cin
    for fout in (4096, 4096, 1000):
        units.append((fin * fout, fout))
        fin = fout
    return units


def resnet50_units():
    """(MACs, output elements) per unit of ResNet-50 v1: the 7×7/2 stem with
    its 3×3/2 max-pool, the bottleneck blocks (1×1 carrying the stage's
    stride, 3×3, 1×1; a projection shortcut on each stage's first block),
    then the global average pool with fc1000."""
    units = [(112 * 112 * 64 * 3 * 49, 56 * 56 * 64)]
    hw, cin = 56, 64
    for blocks, width, cout, stride in ((3, 64, 256, 1), (4, 128, 512, 2),
                                        (6, 256, 1024, 2), (3, 512, 2048, 2)):
        for b in range(blocks):
            hw //= stride if b == 0 else 1
            px = hw * hw
            mac = px * (cin * width + width * width * 9 + width * cout)
            if b == 0:
                mac += px * cin * cout
            units.append((mac, px * cout))
            cin = cout
    units.append((cin * 1000, 1000))
    return units


def _from_units(units):
    macs = np.array([m for m, _ in units], np.int64)
    g = 2 * macs / 1e9
    cum = np.concatenate([[0.0], 2 * np.cumsum(macs) / 1e9])
    act_bits = 8.0 * np.array(
        [INPUT_BYTES] + [ACT_BYTES * e for _, e in units], np.float64)
    bits_per_gflop = float(act_bits[1:].mean()) / float(g.mean())
    return g, cum, act_bits, bits_per_gflop, float(cum[-1])


PROFILES = {
    "cnn60": _cnn60,
    "vgg16": lambda cfg: _from_units(vgg16_units()),
    "resnet50": lambda cfg: _from_units(resnet50_units()),
}


def is_mix(cfg: SwarmConfig) -> bool:
    return len(cfg.task_profiles) > 1


def make_profile(cfg: SwarmConfig):
    """The configured task model: a ``TaskProfile`` for one profile, a
    ``ProfileMix`` for several."""
    names, shares = tuple(cfg.task_profiles), tuple(cfg.task_mix)
    unknown = [p for p in names if p not in PROFILES]
    if not names or unknown or len(set(names)) != len(names):
        raise ValueError(f"task_profiles {names}: distinct names from "
                         f"{sorted(PROFILES)}")
    if len(shares) != len(names) or min(shares) <= 0.0 or \
            abs(sum(shares) - 1.0) > 1e-6:
        raise ValueError(f"task_mix {shares}: one positive share per "
                         f"profile of {names}, summing to 1")
    if cfg.early_exit_enabled and names != ("cnn60",):
        raise ValueError("early exit is defined for the cnn60 profile "
                         f"alone (its exit points), not for {names}")
    profiles = [PROFILES[p](cfg) for p in names]
    if len(profiles) == 1:
        return _as_profile(*profiles[0])
    layers = tuple(len(g) for g, *_ in profiles)
    width = max(layers) + 1
    cum = np.full((len(names), width), np.inf, np.float32)
    act = np.zeros((len(names), width), np.float32)
    for i, (_, c, a, _, _) in enumerate(profiles):
        cum[i, :layers[i] + 1] = c
        act[i, :layers[i] + 1] = a
    bpg = [b for *_, b, _ in profiles]
    return ProfileMix(
        names=names, shares=shares, cum_gflops=cum, act_bits=act,
        done_gflops=np.array([cum[i, L] for i, L in enumerate(layers)],
                             np.float32),
        total_gflops=np.array([t for *_, t in profiles], np.float32),
        bits_per_gflop=np.array(bpg, np.float32),
        idle_bits_per_gflop=sum(s * b for s, b in zip(shares, bpg)))


def pick(table, pid) -> jax.Array:
    """``table[pid]`` for a static ``[P, ...]`` table and per-task ids of
    any shape: a chain of selects over the P rows (no gather)."""
    t = np.asarray(table)
    cond_shape = pid.shape + (1,) * (t.ndim - 1)
    out = jnp.broadcast_to(jnp.asarray(t[0]), pid.shape + t.shape[1:])
    for p in range(1, t.shape[0]):
        out = jnp.where((pid == p).reshape(cond_shape), t[p], out)
    return out


def draw_profiles(key, mix: ProfileMix, n: int) -> jax.Array:
    """A profile id per node for this tick's arrivals, from ``key``: a
    uniform draw against the cumulative shares."""
    u = jax.random.uniform(key, (n,))
    cut = np.cumsum(np.asarray(mix.shares, np.float64))[:-1]
    return jnp.sum(u[:, None] >= cut.astype(np.float32)[None, :], axis=1,
                   dtype=jnp.int32)


def _at_layer(rows, lyr) -> jax.Array:
    """``rows[i, lyr[i]]``: a masked max over the unit axis."""
    hit = jnp.arange(rows.shape[-1]) == lyr[..., None]
    return jnp.max(jnp.where(hit, rows, -jnp.inf), axis=-1)


def layer_of(profile, cum_done: jax.Array,
             pid: Optional[jax.Array] = None) -> jax.Array:
    """Last *completed* layer boundary for a progress value (partial layer
    work does not count — §3.1 discard-on-offload).  A ``ProfileMix`` reads
    each task's own profile ``pid``."""
    if isinstance(profile, ProfileMix):
        with phase("task_profile"):
            cum = pick(profile.cum_gflops, pid)
            return jnp.sum(cum <= cum_done[..., None], axis=-1,
                           dtype=jnp.int32) - 1
    # oob: searchsorted's own CLIP gathers (inside jax) read in-range
    return jnp.searchsorted(profile.cum_gflops, cum_done, side="right") - 1


def boundary_bits(profile, cum_done: jax.Array,
                  pid: Optional[jax.Array] = None) -> jax.Array:
    """Bits that must be shipped when offloading at the current boundary."""
    if isinstance(profile, ProfileMix):
        lyr = layer_of(profile, cum_done, pid)
        with phase("task_profile"):
            return _at_layer(pick(profile.act_bits, pid), lyr)
    lyr = jnp.clip(layer_of(profile, cum_done), 0, profile.act_bits.shape[0] - 1)
    return profile.act_bits[lyr]


def snap_to_boundary(profile, cum_done: jax.Array,
                     pid: Optional[jax.Array] = None) -> jax.Array:
    """Discard partial-layer progress (§3.1)."""
    if isinstance(profile, ProfileMix):
        lyr = layer_of(profile, cum_done, pid)
        with phase("task_profile"):
            return _at_layer(pick(profile.cum_gflops, pid), lyr)
    lyr = jnp.clip(layer_of(profile, cum_done), 0,
                   profile.cum_gflops.shape[0] - 1)
    return profile.cum_gflops[lyr]
