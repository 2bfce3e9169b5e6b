"""Critical-path attribution + load-generator tests (DESIGN.md §14.2,
§14.4): per-task segment reconciliation against latency_s,
stable key sets under degraded inputs, segment attribution,
arrival-process determinism, and the open-loop SLO smoke over the
synthetic serve engine.
"""
import numpy as np
import pytest

from repro.obs.loadgen import (SyntheticServeEngine, mmpp_arrivals,
                               poisson_arrivals, replay_arrivals,
                               run_open_loop)
from repro.obs.slo import slo_indices
from repro.trace import schema
from repro.trace.critical import (SEGMENTS, attribute, decompose,
                                  hop_stall_fraction, segment_indices)
from repro.trace.decode import decode, decode_hops

RNG = np.random.default_rng(3)
TICK = 0.05


def _task_rows(n=400, dropped_every=0, tx_frac=0.3):
    rows = []
    for i in range(n):
        created = float(RNG.uniform(0, 20))
        lat = float(RNG.lognormal(-1.0, 1.0))
        is_drop = dropped_every and i % dropped_every == 0
        rows.append(schema.pack_np(
            i, 0, 1, created, created + lat,
            schema.DROPPED if is_drop else 0,
            0 if is_drop else 30, 2, energy_j=0.1,
            tx_time_s=tx_frac * lat))
    return np.stack(rows)


def _hop_rows(n=200, stall_ticks=2):
    rows = np.zeros((n, schema.NUM_HOP_FIELDS), np.float64)
    rows[:, schema.HOP_SEQ] = np.arange(n)
    rows[:, schema.HOP_T_ARRIVE] = RNG.uniform(0.5, 1.5, size=n)
    rows[:, schema.HOP_BITS] = 1e6
    rows[:, schema.HOP_STALL_TICKS] = stall_ticks
    return rows


# ---------------------------------------------------------------------------
# decompose / segment_indices
# ---------------------------------------------------------------------------

def test_decompose_reconciles_per_task():
    dec = decode(_task_rows(dropped_every=7))
    hdec = decode_hops(_hop_rows())
    seg = decompose(dec, hdec, tick_s=TICK, gflops_per_layer=0.2,
                    capability_gflops=400.0)
    total = sum(seg[name] for name in SEGMENTS)
    np.testing.assert_allclose(total, seg["latency_s"], rtol=0, atol=1e-9)
    assert (seg["latency_s"].size
            == int((~dec["is_dropped"]).sum()))       # completed only
    for name in SEGMENTS:
        assert (seg[name] >= -1e-12).all()


def test_decompose_degrades_keep_sum_exact():
    dec = decode(_task_rows())
    # no hop stream → all in-flight time is airtime
    seg = decompose(dec, None, gflops_per_layer=0.2,
                    capability_gflops=400.0)
    assert float(seg["stall_s"].sum()) == 0.0
    # no compute-rate estimate → compute absorbs on-node, queue-wait 0
    seg2 = decompose(dec)
    assert float(seg2["queue_wait_s"].sum()) == 0.0
    for s in (seg, seg2):
        total = sum(s[name] for name in SEGMENTS)
        np.testing.assert_allclose(total, s["latency_s"],
                                   rtol=0, atol=1e-9)


def test_hop_stall_fraction_bounds():
    hdec = decode_hops(_hop_rows(stall_ticks=0))
    assert hop_stall_fraction(hdec, TICK) == 0.0
    hdec = decode_hops(_hop_rows(stall_ticks=1000))   # stalls > transfer
    assert hop_stall_fraction(hdec, TICK) == 1.0
    empty = decode_hops(np.full((4, schema.NUM_HOP_FIELDS), -1.0))
    assert hop_stall_fraction(empty, TICK) == 0.0


def test_segment_indices_stable_keys():
    dec = decode(_task_rows())
    out = segment_indices(dec, decode_hops(_hop_rows()), tick_s=TICK,
                          gflops_per_layer=0.2, capability_gflops=400.0)
    assert out["task_count"] == 400
    assert out["reconcile_max_err_s"] < 1e-9
    shares = [out[f"{n}_share"] for n in SEGMENTS]
    assert sum(shares) == pytest.approx(1.0)
    # all-dropped trace: same key set, null quantiles, zero shares
    empty = segment_indices(decode(_task_rows(n=5, dropped_every=1)))
    assert sorted(empty) == sorted(out)
    assert empty["task_count"] == 0
    for n in SEGMENTS:
        assert empty[f"{n}_quantiles"] is None
        assert empty[f"{n}_share"] == 0.0


def test_attribute_names_the_moved_segment():
    base = segment_indices(decode(_task_rows()), tick_s=TICK,
                           gflops_per_layer=0.2, capability_gflops=400.0)
    cur = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in base.items()}
    cur["queue_wait_s_quantiles"] = dict(base["queue_wait_s_quantiles"])
    cur["queue_wait_s_quantiles"]["p50"] = \
        base["queue_wait_s_quantiles"]["p50"] + 1.0
    hit = attribute(base, cur)
    assert hit["segment"] == "queue_wait_s"
    assert hit["delta_s"] == pytest.approx(1.0)
    assert attribute(base, base) is None              # nothing regressed
    assert attribute({}, {}) is None                  # nothing comparable


# ---------------------------------------------------------------------------
# arrival processes
# ---------------------------------------------------------------------------

def test_arrivals_deterministic_and_sorted():
    a = poisson_arrivals(500.0, 10.0, seed=4)
    b = poisson_arrivals(500.0, 10.0, seed=4)
    np.testing.assert_array_equal(a, b)
    assert (np.diff(a) >= 0).all() and a[-1] < 10.0
    assert a.size == pytest.approx(5000, rel=0.1)
    m1 = mmpp_arrivals(400.0, 800.0, 20.0, seed=4)
    m2 = mmpp_arrivals(400.0, 800.0, 20.0, seed=4)
    np.testing.assert_array_equal(m1, m2)
    assert (np.diff(m1) >= 0).all()


def test_mmpp_mean_rate_near_dwell_weighted_target():
    # 6 s low at 0.8r, 2 s high at 1.6r → long-run mean r (loadtest.py)
    r = 1000.0
    t = mmpp_arrivals(0.8 * r, 1.6 * r, 200.0, seed=11)
    assert t.size / 200.0 == pytest.approx(r, rel=0.15)


def test_replay_arrivals_clips_and_sorts():
    t = replay_arrivals([3.0, 1.0, -2.0, 9.0], horizon_s=5.0)
    np.testing.assert_array_equal(t, [1.0, 3.0])


# ---------------------------------------------------------------------------
# open-loop SLO smoke (the scheduling-faithful synthetic engine)
# ---------------------------------------------------------------------------

def test_open_loop_slo_smoke():
    eng = SyntheticServeEngine(n_stages=4, max_queue=256)
    times = poisson_arrivals(3000.0, 2.0, seed=1)
    stats = run_open_loop(eng, times, dt=0.01, max_batch=64)
    out = slo_indices(stats, horizon_s=float(eng.clock),
                      offered_rows=int(times.size), rate_rps=3000.0,
                      max_queue=256)
    assert out["completed"] + out["dropped"] == times.size   # full drain
    assert out["drop_rate"] == 0.0                           # sub-capacity
    assert out["goodput_rps"] > 0 and out["latency_s"]["p50"] is not None
    assert out["latency_s"]["p50"] <= out["latency_s"]["p999"]
    assert out["time_to_first_exit_s"] > 0
    assert out["segment_reconcile_err_s"] < 1e-6
    assert out["queue_depth_mean"] is not None
    assert set(out["segments"]) == set(SEGMENTS)


def test_open_loop_overload_drops_and_saturates():
    eng = SyntheticServeEngine(n_stages=2, max_queue=8)
    times = poisson_arrivals(20_000.0, 1.0, seed=2)   # ~3x capacity
    stats = run_open_loop(eng, times, dt=0.01, max_batch=64)
    out = slo_indices(stats, horizon_s=float(eng.clock),
                      offered_rows=int(times.size), max_queue=8)
    assert out["dropped"] > 0 and out["drop_rate"] > 0
    # state snapshots land after the epoch's service, so the sampled max
    # sits one batch under the admission bound
    assert out["queue_saturation"] >= 0.8
    assert out["completed"] + out["dropped"] == stats.generated_rows


def test_slo_indices_zero_completions_well_defined():
    eng = SyntheticServeEngine(n_stages=2)
    out = slo_indices(eng.stats, horizon_s=0.0, offered_rows=0)
    assert out["avg_latency_s"] is None               # not NaN in JSON
    assert out["time_to_first_exit_s"] is None
    assert out["goodput_rps"] == 0.0 and out["drop_rate"] == 0.0
    assert out["latency_s"]["p50"] is None
