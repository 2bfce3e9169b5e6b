"""Hop-stream telemetry tests (DESIGN.md §10.5) mirroring the TaskRecord
suite: hop-capture-off invariance, delivery accounting against the scalar
accumulators, bit-identical hop records across all three executor
backends, overflow exactness, interrupt/resume preservation — plus the
transfer-accounting regressions this PR fixes (contended-delivery energy
freeze, delivered-transfer denominator, stable report key sets).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.fleet import (ResultStore, SweepInterrupted, SweepSpec,
                         point_digest, run_batch, run_point)
from repro.swarm import DISTRIBUTED, make_profile
from repro.swarm import simulator as sim
from repro.swarm import transfer as transfer_mod
from repro.trace import (decode, decode_hops, hop_airtime_s, hop_energy_j,
                         hop_indices, link_energy_j, schema, split_runs,
                         trace_indices)

KEY = jax.random.PRNGKey(0)
N, RUNS = 8, 6
CFG = dataclasses.replace(SwarmConfig(), sim_time_s=2.0, num_workers=N)
CFG_HOP = dataclasses.replace(CFG, trace_hop_capacity=512)
CFG_BOTH = dataclasses.replace(CFG_HOP, trace_capacity=512)


def _np(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


@pytest.fixture(scope="module")
def hopped():
    return _np(run_batch(KEY, CFG_HOP, jnp.int32(DISTRIBUTED), N, RUNS))


@pytest.fixture(scope="module")
def plain():
    return _np(run_batch(KEY, CFG, jnp.int32(DISTRIBUTED), N, RUNS))


# ---------------------------------------------------------------------------
# hop capture off == no hop state; on perturbs nothing
# ---------------------------------------------------------------------------


def test_capacity_zero_emits_no_hop_state(plain):
    assert not any(k.startswith("trace_") for k in plain)


def test_hop_capture_does_not_perturb_metrics(hopped, plain):
    for k in plain:
        np.testing.assert_array_equal(hopped[k], plain[k], err_msg=k)


def test_hop_stream_independent_of_task_stream(hopped):
    """Either stream can be on without the other; the hop buffers are
    bit-identical both ways."""
    both = _np(run_batch(KEY, CFG_BOTH, jnp.int32(DISTRIBUTED), N, RUNS))
    np.testing.assert_array_equal(both["trace_hops"], hopped["trace_hops"])
    np.testing.assert_array_equal(both["trace_hop_overflow"],
                                  hopped["trace_hop_overflow"])


# ---------------------------------------------------------------------------
# hop accounting vs the scalar accumulators
# ---------------------------------------------------------------------------


def test_hops_account_for_every_delivery(hopped):
    """records + overflow == delivered transfers (in-flight-at-end hops
    are neither), and per-hop times reproduce the delivered-mean metric."""
    hdec = decode_hops(hopped["trace_hops"], hopped["trace_hop_overflow"])
    delivered = hopped["transfers_delivered"].sum()
    assert hdec["seq"].size + int(hdec["overflow"]) == int(delivered)
    assert np.all(hopped["transfers_delivered"] <= hopped["transfers"])
    # tx_time_sum == Σ per-hop (t_arrive - t_depart), per run
    per_run = split_runs(hopped["trace_hops"], hops=True)
    tsum = (hopped["avg_transfer_time_s"]
            * np.maximum(hopped["transfers_delivered"], 1.0))
    for run, s, d in zip(per_run, tsum, hopped["transfers_delivered"], strict=True):
        if d > 0:
            assert np.isclose(run["transfer_time_s"].sum(), s, rtol=1e-4)
        assert np.all(np.diff(run["seq"]) > 0)   # scatter-by-seq ordering


def test_hop_fields_are_physical(hopped):
    hdec = decode_hops(hopped["trace_hops"], hopped["trace_hop_overflow"])
    assert np.all(hdec["t_arrive"] > hdec["t_depart"])
    assert np.all((hdec["src"] >= 0) & (hdec["src"] < N))
    assert np.all((hdec["dst"] >= 0) & (hdec["dst"] < N))
    assert np.all(hdec["src"] != hdec["dst"])
    assert np.all(hdec["bits"] > 0)
    assert np.all(hdec["boundary_layer"] >= 0)
    assert np.all(hdec["boundary_layer"] <= CFG.task_layers)
    assert np.all(hdec["stall_ticks"] >= 0)
    # stalls never exceed the hop's own duration
    assert np.all(hdec["stall_ticks"] * CFG.tick_s
                  <= hdec["transfer_time_s"] + 1e-6)


def test_hop_overflow_saturates_capture_exactly():
    cap = 4
    cfg = dataclasses.replace(CFG_HOP, trace_hop_capacity=cap)
    m = _np(run_batch(KEY, cfg, jnp.int32(DISTRIBUTED), N, 3))
    hdec = decode_hops(m["trace_hops"], m["trace_hop_overflow"])
    delivered = m["transfers_delivered"].sum()
    assert int(hdec["overflow"]) > 0
    assert hdec["seq"].size + int(hdec["overflow"]) == int(delivered)
    assert np.all(hdec["seq"] < cap)
    # the captured prefix agrees with the uncapped run, record for record
    full = _np(run_batch(KEY, CFG_HOP, jnp.int32(DISTRIBUTED), N, 3))
    for small, big in zip(split_runs(m["trace_hops"], hops=True),
                          split_runs(full["trace_hops"], hops=True),
                          strict=True):
        keep = big["seq"] < cap
        for f in schema.HOP_FIELDS:
            np.testing.assert_array_equal(small[f], big[f][keep],
                                          err_msg=f)


# ---------------------------------------------------------------------------
# backends + resume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,kw", [("sharded", {}),
                                        ("streaming", {"chunk_size": 4})])
def test_hops_bit_identical_across_backends(hopped, backend, kw):
    got = _np(run_batch(KEY, CFG_HOP, jnp.int32(DISTRIBUTED), N, RUNS,
                        backend=backend, **kw))
    np.testing.assert_array_equal(got["trace_hops"], hopped["trace_hops"])
    np.testing.assert_array_equal(got["trace_hop_overflow"],
                                  hopped["trace_hop_overflow"])


def test_interrupted_streaming_sweep_preserves_hops(tmp_path, hopped,
                                                    monkeypatch):
    monkeypatch.setenv("REPRO_CODE_VERSION", "test-hops")
    from repro.fleet.store import code_version
    code_version.cache_clear()
    spec = SweepSpec.build("hopresume", CFG_HOP,
                           strategies=(DISTRIBUTED,), num_runs=RUNS)
    (pt,) = spec.expand()
    store = ResultStore(str(tmp_path))
    with pytest.raises(SweepInterrupted):
        run_point(pt, backend="streaming", store=store, chunk_size=2,
                  max_chunks=1)
    done, accum = store.load_partial(point_digest(pt))
    assert done == 1
    assert accum["trace_hops"].shape == (2, 512, schema.NUM_HOP_FIELDS)
    resumed = run_point(pt, backend="streaming", store=store, chunk_size=2)
    np.testing.assert_array_equal(resumed["trace_hops"],
                                  hopped["trace_hops"])
    # store round-trip (trailing-slot compaction) preserves every record
    hit = run_point(pt, backend="vmap", store=store)
    dh = decode_hops(hit["trace_hops"])
    dt = decode_hops(hopped["trace_hops"])
    for f in schema.HOP_FIELDS:
        np.testing.assert_array_equal(dh[f], dt[f], err_msg=f)
    code_version.cache_clear()


# ---------------------------------------------------------------------------
# transfer-accounting regressions (the PR's bugfix satellites)
# ---------------------------------------------------------------------------


def _contention_state(cfg, bits, rate):
    """Two senders (0, 1) -> one receiver (2), same bits, same tick."""
    st = sim.init_state(jax.random.PRNGKey(1), cfg, 3)
    st = dict(st)
    st["tx_active"] = jnp.asarray([True, True, False])
    st["tx_dst"] = jnp.asarray([2, 2, 0], jnp.int32)
    st["tx_bits"] = jnp.asarray([bits, bits, 0.0], jnp.float32)
    st["tx_start"] = jnp.zeros((3,), jnp.float32)
    st["tx_count"] = jnp.int32(2)      # event counters carry as i32 (J001)
    if "hop_seq" in st:
        st["hop_seq"] = jnp.asarray([0, 1, 0], jnp.int32)
        st["hop_bits"] = st["tx_bits"]
        st["hop_counter"] = jnp.int32(2)
    rate = jnp.full((3,), rate, jnp.float32)
    alive = jnp.ones((3,), bool)
    return st, rate, alive


def test_contended_delivery_energy_pins_to_single_transfer_value():
    """The loser of receiver contention must stop accruing airtime energy
    once its payload has fully arrived: both tasks cost exactly one tick
    of transmit power, and tx_bits never runs below zero forever."""
    cfg = dataclasses.replace(SwarmConfig(), num_workers=3,
                              trace_capacity=64, trace_hop_capacity=64)
    tick = cfg.tick_s
    tx_w = 10.0 ** (cfg.tx_power_dbm / 10.0) * 1e-3
    # both payloads arrive within one tick
    st, rate, alive = _contention_state(cfg, bits=100.0, rate=100.0 / tick)
    st = transfer_mod.progress(st, rate, alive, cfg, tick)        # tick 1
    assert bool(st["tx_active"][1]) and not bool(st["tx_active"][0])
    assert float(jnp.sum(st["e_tx"])) == pytest.approx(2 * tx_w * tick)
    bits_frozen = float(st["tx_bits"][1])
    st = transfer_mod.progress(st, rate, alive, cfg, 2 * tick)    # tick 2
    assert not bool(st["tx_active"][1])                          # delivered
    # no further accrual for the waiting tick, bits frozen at arrival
    assert float(jnp.sum(st["e_tx"])) == pytest.approx(2 * tx_w * tick)
    assert float(st["tx_bits"][1]) == pytest.approx(bits_frozen)
    # per-task attribution matches: loser pays the same as the winner
    assert float(st["tx_energy"][0]) == pytest.approx(tx_w * tick)
    assert float(st["tx_energy"][1]) == pytest.approx(tx_w * tick)
    # the delivery wait is kept: loser's transfer time is one tick longer
    assert float(st["tx_delivered"]) == 2.0
    assert float(st["tx_time_sum"]) == pytest.approx(tick + 2 * tick)
    # hop records: winner stalled 0 ticks, loser 1 (the contention wait)
    hdec = decode_hops(np.asarray(st["trace_hops"]))
    assert hdec["seq"].size == 2
    assert hdec["stall_ticks"].tolist() == [0, 1]
    assert np.allclose(hdec["transfer_time_s"], [tick, 2 * tick])


def test_avg_transfer_time_uses_delivered_denominator():
    """An in-flight transfer at sim end must not drag the mean down."""
    cfg = dataclasses.replace(SwarmConfig(), num_workers=3)
    profile = make_profile(cfg)
    tick = cfg.tick_s
    st, rate, alive = _contention_state(cfg, bits=100.0, rate=100.0 / tick)
    # sender 1 now targets a different receiver but with a huge payload:
    # it is still in flight when the sim ends
    st["tx_dst"] = jnp.asarray([2, 0, 0], jnp.int32)
    st["tx_bits"] = jnp.asarray([100.0, 1e12, 0.0], jnp.float32)
    st = transfer_mod.progress(st, rate, alive, cfg, tick)
    out = {k: float(v) for k, v in sim.summarize(st, cfg, profile).items()}
    assert out["transfers"] == 2.0
    assert out["transfers_delivered"] == 1.0
    # delivered mean is the delivered transfer's time — not halved by the
    # still-in-flight initiation
    assert out["avg_transfer_time_s"] == pytest.approx(tick)


def test_hop_energy_join_reproduces_e_tx():
    """Per-hop airtime-J attribution joins back to the scalar ``e_tx``
    accumulator exactly once every transfer delivers: both contenders pay
    two flying ticks of transmit power; the loser's extra stalled tick
    costs wall time but no energy."""
    cfg = dataclasses.replace(SwarmConfig(), num_workers=3,
                              trace_hop_capacity=64)
    tick = cfg.tick_s
    tx_w = 10.0 ** (cfg.tx_power_dbm / 10.0) * 1e-3
    st, rate, alive = _contention_state(cfg, bits=100.0,
                                       rate=100.0 / (2 * tick))
    for i in range(1, 8):
        st = transfer_mod.progress(st, rate, alive, cfg, i * tick)
    assert float(st["tx_delivered"]) == 2.0
    hdec = decode_hops(np.asarray(st["trace_hops"]))
    air = hop_airtime_s(hdec, tick)
    e = hop_energy_j(hdec, tick, cfg.tx_power_dbm)
    np.testing.assert_allclose(e, air * tx_w)
    assert e.sum() == pytest.approx(float(jnp.sum(st["e_tx"])))
    # the stall is excluded: the loser's wall clock exceeds its airtime
    assert np.any(air < hdec["transfer_time_s"])
    # per-link rollup is the same join, grouped by directed link
    le = link_energy_j(hdec, tick, cfg.tx_power_dbm)
    assert set(le) == {"0->2", "1->2"}
    assert sum(le.values()) == pytest.approx(float(jnp.sum(st["e_tx"])))


def test_hop_energy_in_report_and_schema(hopped):
    """``tx_power_dbm`` fills the airtime-energy entries; without it the
    keys are present but None (stable BENCH schema either way)."""
    from repro.fleet import build_report
    doc = build_report({"pt": hopped}, tick_s=CFG.tick_s,
                       tx_power_dbm=CFG.tx_power_dbm)["points"]["pt"]
    assert doc["hop_energy_j_quantiles"]["p50"] > 0
    assert doc["link_energy_j_quantiles"]["p50"] > 0
    assert doc["tx_energy_total_j"] > 0
    assert doc["tx_airtime_total_s"] > 0
    tx_w = 10.0 ** (CFG.tx_power_dbm / 10.0) * 1e-3
    assert doc["tx_energy_total_j"] == pytest.approx(
        doc["tx_airtime_total_s"] * tx_w)
    bare = build_report({"pt": hopped}, tick_s=CFG.tick_s)["points"]["pt"]
    assert sorted(bare) == sorted(doc)
    assert bare["tx_airtime_total_s"] is not None   # needs only tick_s
    assert bare["tx_energy_total_j"] is None
    assert bare["hop_energy_j_quantiles"] is None


def test_trace_indices_schema_is_stable():
    """An all-drop trace must emit the same key set as a populated one
    (empty histograms / null quantiles), so BENCH diffs stay comparable."""
    drop_row = schema.pack_np(0, 1, 2, 0.0, 0.5, schema.DROPPED, 0, 1)
    done_row = schema.pack_np(1, 0, 0, 0.0, 0.2, 0, 60, 0)
    all_drop = trace_indices(decode(np.asarray([drop_row])))
    populated = trace_indices(decode(np.asarray([drop_row, done_row])))
    assert sorted(all_drop) == sorted(populated)
    assert all_drop["task_count"] == 0
    assert all_drop["task_latency_cdf_s"] is None
    assert all_drop["task_latency_jain"] is None
    assert all_drop["hop_histogram"] == {}
    assert populated["task_latency_cdf_s"] is not None
    # the hop section has the same guarantee
    empty = hop_indices(decode_hops(schema.empty_hop_buffer(4)))
    full = hop_indices(decode_hops(np.asarray(
        [[0, 0, 1, 0.0, 0.1, 8e6, 3, 2]], np.float32)), tick_s=0.01)
    assert sorted(empty) == sorted(full)
    assert empty["hop_count"] == 0
    assert empty["hop_transfer_time_s_quantiles"] is None
    assert full["hop_queue_wait_s_quantiles"]["p50"] == pytest.approx(0.02)
    assert full["hop_in_flight_s_quantiles"]["p50"] == pytest.approx(0.08)


# ---------------------------------------------------------------------------
# report + export surfaces
# ---------------------------------------------------------------------------


def test_report_gains_hop_resolved_indices(hopped, plain):
    from repro.fleet import build_report
    doc = build_report({"pt": hopped},
                       tick_s=CFG.tick_s)["points"]["pt"]
    assert "trace_hops" not in doc          # buffers aggregated, not dumped
    hdec = decode_hops(hopped["trace_hops"], hopped["trace_hop_overflow"])
    assert doc["hop_count"] == hdec["seq"].size
    assert doc["hop_transfer_time_s_quantiles"]["p50"] == pytest.approx(
        float(np.quantile(hdec["transfer_time_s"], 0.5)))
    assert doc["hop_queue_wait_s_quantiles"] is not None
    # un-hopped points keep their historical shape: no hop-level section
    doc0 = build_report({"pt": plain})["points"]["pt"]
    assert not any(k.startswith("hop_") for k in doc0)


def test_perhop_chrome_trace_export(tmp_path):
    import json
    from repro.trace import write_chrome_trace
    m = _np(run_batch(KEY, CFG_BOTH, jnp.int32(DISTRIBUTED), N, 1))
    dec = decode(m["trace_records"][0], m["trace_overflow"][0])
    hdec = decode_hops(m["trace_hops"][0], m["trace_hop_overflow"][0])
    path = write_chrome_trace(str(tmp_path / "t.json"), dec, hdec,
                              CFG.tick_s)
    with open(path) as f:
        doc = json.load(f)                  # validates as JSON
    ev = doc["traceEvents"]
    hops = [e for e in ev if e.get("cat") == "hop"]
    flows = [e for e in ev if e.get("cat") == "transfer"]
    queues = [e for e in ev if e.get("cat") == "queue"]
    # one slice + one flow arrow (s/f pair) per delivered hop — not per task
    assert len(hops) == hdec["seq"].size
    assert len(flows) == 2 * hdec["seq"].size
    # in-flight slices live on the sender's track
    assert all(e["tid"] == e["args"]["src"] for e in hops)
    assert all(e["dur"] >= 0 for e in hops)
    # one queue-wait slice per stalled hop, on the visited receiver track
    assert len(queues) == int((hdec["stall_ticks"] > 0).sum())
    assert all(e["tid"] == e["args"]["dst"] for e in queues)
    assert all(e["dur"] > 0 for e in queues)
