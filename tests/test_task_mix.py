"""Per-task layer profiles (swarm/tasks.py): the published networks' layer
tables, the one-profile program left as it was, and a VGG-16 / ResNet-50
mix against its plain reference (bench/reference/swarm_mix.py)."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import swarm_mix as mix_ref
from repro.configs.base import SwarmConfig
from repro.fleet import executor
from repro.swarm import run_many, transfer
from repro.swarm.simulator import (DISTRIBUTED, LOCAL_ONLY, RANDOM_ACYCLIC,
                                   STRATEGY_NAMES, init_state)
from repro.swarm.tasks import (ProfileMix, draw_profiles, make_profile,
                               resnet50_units, vgg16_units)

MIX = dataclasses.replace(SwarmConfig(), num_workers=8,
                          task_profiles=("vgg16", "resnet50"),
                          task_mix=(0.5, 0.5))

# activation bytes crossing each boundary (the uint8 input first), float32
VGG16_BYTES = [150528, 12845056, 3211264, 6422528, 1605632, 3211264,
               3211264, 802816, 1605632, 1605632, 401408, 401408, 401408,
               100352, 16384, 16384, 4000]
RESNET50_BYTES = ([150528, 802816] + [3211264] * 3 + [1605632] * 4
                  + [802816] * 6 + [401408] * 3 + [4000])


@pytest.mark.parametrize("units, n_units, macs, act_bytes", [
    (vgg16_units, 16, 15_470_264_320, VGG16_BYTES),
    (resnet50_units, 18, 3_857_973_248, RESNET50_BYTES)])
def test_layer_tables(units, n_units, macs, act_bytes):
    u = units()
    assert len(u) == n_units
    assert sum(m for m, _ in u) == macs
    assert [150528] + [4 * e for _, e in u] == act_bytes
    name = units.__name__.replace("_units", "")
    p = make_profile(dataclasses.replace(SwarmConfig(), task_profiles=(name,)))
    assert p.gflops.shape == (n_units,)
    assert float(p.cum_gflops[-1]) == pytest.approx(2 * macs / 1e9, rel=1e-7)
    assert np.asarray(p.act_bits).tolist() == [8.0 * b for b in act_bytes]


@pytest.mark.parametrize("table, units, macs", [
    (mix_ref.vgg16_table, vgg16_units, 15_470_264_320),
    (mix_ref.resnet50_table, resnet50_units, 3_857_973_248)])
def test_reference_tables_agree(table, units, macs):
    """The reference's tables, written out from the papers, and the
    program's, built by loops, give the same units."""
    assert [(m, 4 * e) for m, e in units()] == table()
    assert sum(m for m, _ in table()) == macs


# sha256 over the sorted outputs (names and bytes) of run_many(PRNGKey(3),
# N = 8, 2 s, 3 runs, task and hop streams on) per strategy, as the program
# gave them before per-task profiles existed (jax 0.9.0, CPU)
_ONE_PROFILE_PIN = {
    0: "a6de136817c13add4145c20cd54547ec05569b5f8bdc9bfd4682e29cef2031bd",
    1: "6b19b174119523639736028f2b4cbcff0cdde5a6e3955605b8f81090c2dc0a01",
    2: "b1124861b540aec9f96a5a7eca82376b4d2391c07c23996376fa53ce9b1cf460",
    3: "29d2dc1d7122edf71fbc9ba7e51ef88a00147754795cd36b3883e86632a7d9df",
    4: "9488733b6858e6d1654fe29e67cd3fb7469e14299544aa9f90320cdd875ed378"}


@pytest.mark.parametrize("strategy", range(5), ids=STRATEGY_NAMES)
def test_one_profile_is_the_historical_program(strategy):
    cfg = dataclasses.replace(SwarmConfig(), num_workers=8, sim_time_s=2.0,
                              trace_capacity=256, trace_hop_capacity=256)
    assert cfg.task_profiles == ("cnn60",)
    out = run_many(jax.random.PRNGKey(3), cfg, jnp.int32(strategy), 8, 3)
    h = hashlib.sha256()
    for k in sorted(out):
        h.update(k.encode())
        h.update(np.asarray(out[k]).tobytes())
    assert h.hexdigest() == _ONE_PROFILE_PIN[strategy]


def test_one_profile_traces_no_profile_state():
    """No per-task profile field and no op under ``task_profile`` in the
    one-profile program; the mix program has both."""
    base = dataclasses.replace(SwarmConfig(), num_workers=8, sim_time_s=1.0)
    mix = dataclasses.replace(MIX, sim_time_s=1.0)
    st = init_state(jax.random.PRNGKey(0), base, 8)
    assert not {"q_profile", "tx_profile", "done_by_profile"} & set(st)
    assert "task_profile" not in set(executor.op_scopes(base, 8, 2).values())
    st = init_state(jax.random.PRNGKey(0), mix, 8)
    assert st["q_profile"].shape == (8, mix.queue_slots)
    assert "task_profile" in set(executor.op_scopes(mix, 8, 2).values())


@pytest.mark.parametrize("strategy", [DISTRIBUTED, RANDOM_ACYCLIC,
                                      LOCAL_ONLY],
                         ids=lambda s: STRATEGY_NAMES[s])
def test_mix_matches_reference(strategy):
    cfg = dataclasses.replace(MIX, sim_time_s=10.0, num_runs=2)
    key = jax.random.PRNGKey(11)
    got = run_many(key, cfg, jnp.int32(strategy), 8, 2)
    settings = {f.name: getattr(cfg, f.name)
                for f in dataclasses.fields(cfg)}
    settings = {k: list(v) if isinstance(v, tuple) else v
                for k, v in settings.items()}
    want = mix_ref.run_keys(jax.random.split(key, 2), settings,
                            STRATEGY_NAMES[strategy])
    assert set(want) == set(mix_ref.stats_of(settings))
    assert float(np.sum(want["completed_vgg16"])) > 0
    assert float(np.sum(want["completed_resnet50"])) > 0
    if strategy != LOCAL_ONLY:
        assert float(np.sum(want["transfers_delivered"])) > 0
    for k, w in want.items():
        np.testing.assert_array_equal(np.asarray(got[k], np.float32), w,
                                      err_msg=k)


def _state_with_heads(cfg, profile_ids, cum):
    """A state whose node i holds one task of profile ``profile_ids[i]``
    at progress ``cum[i]`` in slot 0."""
    n = len(profile_ids)
    st = init_state(jax.random.PRNGKey(0), cfg, n)
    st["q_active"] = st["q_active"].at[:, 0].set(True)
    st["q_cum"] = st["q_cum"].at[:, 0].set(jnp.asarray(cum, jnp.float32))
    st["q_profile"] = st["q_profile"].at[:, 0].set(
        jnp.asarray(profile_ids, jnp.int32))
    st["q_seq"] = st["q_seq"].at[:, 0].set(jnp.arange(n, dtype=jnp.int32))
    return st


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "lists"])
def test_offloaded_task_keeps_its_profile(dense):
    cfg = dataclasses.replace(MIX, num_workers=4)
    mix = make_profile(cfg)
    assert isinstance(mix, ProfileMix)
    # node 0: ResNet-50 past its stem; node 1: VGG-16 past conv1_1 and half
    # of conv1_2; both offload to node 3, which holds a VGG-16 task (node 0
    # wins the delivery)
    stem = 2 * 118013952 / 1e9
    conv1 = 2 * (86704128 + 0.5 * 1849688064) / 1e9
    st = _state_with_heads(cfg, [1, 0, 0, 0], [stem + 0.01, conv1, 0.0, 0.0])
    elig = jnp.array([True, True, False, False])
    st = transfer.initiate(st, elig, jnp.array([3, 3, 0, 0], jnp.int32),
                           0.0, mix)
    assert st["tx_profile"][:2].tolist() == [1, 0]
    assert float(st["tx_bits"][0]) == 8.0 * RESNET50_BYTES[1]
    assert float(st["tx_bits"][1]) == 8.0 * VGG16_BYTES[1]
    assert float(st["tx_cum"][0]) == float(mix.cum_gflops[1, 1])
    assert float(st["tx_cum"][1]) == float(mix.cum_gflops[0, 1])
    st["tx_bits"] = jnp.where(elig, 0.0, st["tx_bits"])
    rate = jnp.full((4,), 1e9, jnp.float32)
    st = transfer.progress(st, rate, jnp.ones((4,), bool), cfg, 0.01,
                           dense=dense)
    assert st["q_active"][3, :3].tolist() == [True, True, False]
    assert st["q_profile"][3, :2].tolist() == [0, 1]
    assert float(st["q_cum"][3, 1]) == float(mix.cum_gflops[1, 1])
    assert bool(st["tx_active"][1]) and not bool(st["tx_active"][0])


@pytest.mark.parametrize("shares", [(0.5, 0.5), (0.2, 0.3, 0.5)])
def test_profile_shares_and_determinism(shares):
    names = ("vgg16", "resnet50", "cnn60")[:len(shares)]
    mix = make_profile(dataclasses.replace(SwarmConfig(), task_profiles=names,
                                           task_mix=shares))
    key = jax.random.PRNGKey(5)
    n = 200_000
    ids = np.asarray(draw_profiles(key, mix, n))
    for p, s in enumerate(shares):
        assert abs(np.mean(ids == p) - s) < 4 * np.sqrt(s * (1 - s) / n)
    assert np.array_equal(ids, np.asarray(draw_profiles(key, mix, n)))
    other = np.asarray(draw_profiles(jax.random.PRNGKey(6), mix, n))
    assert not np.array_equal(ids, other)


@pytest.mark.parametrize("profiles, mix", [
    (("vgg16", "resnet50"), (0.5, 0.5)), (("vgg16",), (1.0,))])
def test_early_exit_needs_the_cnn60_profile(profiles, mix):
    cfg = dataclasses.replace(SwarmConfig(), num_workers=4, sim_time_s=0.4,
                              early_exit_enabled=True,
                              task_profiles=profiles, task_mix=mix)
    with pytest.raises(ValueError, match="early exit"):
        make_profile(cfg)
    with pytest.raises(ValueError, match="early exit"):
        run_many(jax.random.PRNGKey(0), cfg, jnp.int32(DISTRIBUTED), 4, 1)


@pytest.mark.parametrize("profiles, mix", [
    (("vgg16", "alexnet"), (0.5, 0.5)), (("vgg16", "vgg16"), (0.5, 0.5)),
    (("vgg16", "resnet50"), (0.7, 0.7)), (("vgg16", "resnet50"), (1.0,))])
def test_bad_mix_raises(profiles, mix):
    with pytest.raises(ValueError):
        make_profile(dataclasses.replace(SwarmConfig(),
                                         task_profiles=profiles,
                                         task_mix=mix))
