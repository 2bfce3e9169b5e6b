"""Flight-recorder tests (DESIGN.md §12): off-invariance, buffer shapes
and stride/subsample exactness, accounting against the scalar
accumulators, bit-identical state buffers across all three executor
backends, kill/resume preservation through the store (SweepInterrupted
and a real SIGKILL'd spawned worker), report/export surfaces, the
shared-schema serve gauges, and the compile/execute spans of computed
points.
"""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SwarmConfig
from repro.fleet import (ResultStore, SweepInterrupted, SweepSpec,
                         build_report, collect, dispatch, execute,
                         point_digest, read_progress, run_batch, run_point,
                         spawn_workers, write_bench_json)
from repro.swarm import DISTRIBUTED
from repro.trace import (decode_state, schema, state_counter_events,
                         state_indices, write_chrome_trace)

KEY = jax.random.PRNGKey(0)
N, RUNS = 8, 6
CFG = dataclasses.replace(SwarmConfig(), sim_time_s=2.0, num_workers=N)
CFG_ST = dataclasses.replace(CFG, trace_state_every=1)
N_EPOCHS = int(round(CFG.sim_time_s / CFG.decision_period_s))
SPEC_KILL = SweepSpec.build(
    "statekill", dataclasses.replace(CFG, sim_time_s=1.0, num_workers=6,
                                     trace_state_every=2),
    axes={"gamma": (0.02, 0.1)}, strategies=(0, 4), num_runs=3)


@pytest.fixture(scope="module", autouse=True)
def _pinned_code_version():
    """Digests must agree with spawned workers and not drift mid-run."""
    from repro.fleet.store import code_version
    old = os.environ.get("REPRO_CODE_VERSION")
    os.environ["REPRO_CODE_VERSION"] = "test-state"
    code_version.cache_clear()
    yield
    if old is None:
        del os.environ["REPRO_CODE_VERSION"]
    else:
        os.environ["REPRO_CODE_VERSION"] = old
    code_version.cache_clear()


def _np(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


@pytest.fixture(scope="module")
def recorded():
    return _np(run_batch(KEY, CFG_ST, jnp.int32(DISTRIBUTED), N, RUNS))


@pytest.fixture(scope="module")
def plain():
    return _np(run_batch(KEY, CFG, jnp.int32(DISTRIBUTED), N, RUNS))


@pytest.fixture(scope="module")
def sdec(recorded):
    return decode_state(recorded["trace_state"],
                        recorded["trace_state_sys"],
                        recorded["trace_state_epochs"])


# ---------------------------------------------------------------------------
# recorder off == historical simulator; recorder on perturbs nothing
# ---------------------------------------------------------------------------


def test_stride_zero_emits_no_state_buffers(plain):
    assert not any(k.startswith("trace_state") for k in plain)
    assert "state_e_tx" not in plain


def test_recording_does_not_perturb_metrics(recorded, plain):
    """The flight recorder must be observation, not intervention: every
    scalar metric of a recorded run is bit-identical to the plain run."""
    for k in plain:
        np.testing.assert_array_equal(recorded[k], plain[k], err_msg=k)
    assert "state_e_tx" not in recorded     # working accumulator, not output


# ---------------------------------------------------------------------------
# buffer shapes, epoch map, gauge accounting vs the scalar accumulators
# ---------------------------------------------------------------------------


def test_state_buffer_shapes_and_epoch_map(recorded):
    assert recorded["trace_state"].shape == \
        (RUNS, N_EPOCHS, N, schema.NUM_STATE_GAUGES)
    assert recorded["trace_state_sys"].shape == \
        (RUNS, N_EPOCHS, schema.NUM_SYS_GAUGES)
    assert recorded["trace_state_epochs"].shape == (RUNS, N_EPOCHS)
    np.testing.assert_array_equal(recorded["trace_state_epochs"][0],
                                  np.arange(N_EPOCHS, dtype=np.float32))


def test_state_gauges_are_physical(sdec):
    assert np.all(sdec["queue_depth"] >= 0)
    assert np.all(sdec["queue_depth"] <= CFG.queue_slots)
    assert np.all((sdec["alive"] == 0) | (sdec["alive"] == 1))
    assert np.all(sdec["e_comp_j"] >= 0) and np.all(sdec["e_tx_j"] >= 0)
    # cumulative gauges never decrease along the epoch axis
    for k in ("e_comp_j", "e_tx_j"):
        assert np.all(np.diff(sdec[k], axis=1) >= -1e-6), k
    for k in ("completed", "dropped", "generated", "energy_j"):
        assert np.all(np.diff(sdec[k], axis=1) >= -1e-6), k
    jain = sdec["queue_jain"]
    assert np.all((jain >= 0) & (jain <= 1.0001))
    assert np.all(jain[sdec["queue_depth_mean"] > 0] > 0)
    np.testing.assert_allclose(
        sdec["t"][0], (np.arange(N_EPOCHS) + 1) * CFG.decision_period_s,
        rtol=1e-5)


def test_final_sample_pins_the_scalar_accumulators(recorded, sdec):
    """The last system sample *is* the end-of-mission accounting: counters
    bit-equal, energy f32-equal, and the per-node cumulative energy
    gauges sum back to the scalar totals."""
    np.testing.assert_array_equal(sdec["completed"][:, -1],
                                  recorded["completed"])
    np.testing.assert_array_equal(sdec["dropped"][:, -1],
                                  recorded["dropped"])
    np.testing.assert_array_equal(
        sdec["energy_j"][:, -1].astype(np.float32),
        recorded["energy_total_j"])
    per_node = sdec["e_comp_j"][:, -1, :] + sdec["e_tx_j"][:, -1, :]
    np.testing.assert_allclose(per_node.sum(axis=1),
                               recorded["energy_total_j"], rtol=1e-4)


def test_stride_and_subsample_are_exact_slices(recorded):
    """every=3 / nodes=4 records exactly the full stream's sampled epochs
    and node prefix — subsampling selects, never re-aggregates."""
    cfg = dataclasses.replace(CFG, trace_state_every=3,
                              trace_state_nodes=4)
    m = _np(run_batch(KEY, cfg, jnp.int32(DISTRIBUTED), N, RUNS))
    S = -(-N_EPOCHS // 3)
    assert m["trace_state"].shape == (RUNS, S, 4, schema.NUM_STATE_GAUGES)
    np.testing.assert_array_equal(m["trace_state_epochs"][0],
                                  np.arange(0, N_EPOCHS, 3))
    np.testing.assert_array_equal(
        m["trace_state"], recorded["trace_state"][:, ::3, :4])
    np.testing.assert_array_equal(
        m["trace_state_sys"], recorded["trace_state_sys"][:, ::3])


# ---------------------------------------------------------------------------
# acceptance: buffers bit-identical across all three executor backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,kw", [("sharded", {}),
                                        ("streaming", {"chunk_size": 4})])
def test_state_bit_identical_across_backends(recorded, backend, kw):
    got = _np(run_batch(KEY, CFG_ST, jnp.int32(DISTRIBUTED), N, RUNS,
                        backend=backend, **kw))
    for k in ("trace_state", "trace_state_sys", "trace_state_epochs"):
        np.testing.assert_array_equal(got[k], recorded[k], err_msg=k)


# ---------------------------------------------------------------------------
# store/resume: buffers survive interrupts and SIGKILL'd workers
# ---------------------------------------------------------------------------


def test_interrupted_streaming_sweep_preserves_state(tmp_path, recorded):
    spec = SweepSpec.build("stateresume", CFG_ST,
                           strategies=(DISTRIBUTED,), num_runs=RUNS)
    (pt,) = spec.expand()
    store = ResultStore(str(tmp_path))
    with pytest.raises(SweepInterrupted):
        run_point(pt, backend="streaming", store=store, chunk_size=2,
                  max_chunks=1)
    done, accum = store.load_partial(point_digest(pt))
    assert done == 1
    assert accum["trace_state"].shape == \
        (2, N_EPOCHS, N, schema.NUM_STATE_GAUGES)
    resumed = run_point(pt, backend="streaming", store=store, chunk_size=2)
    np.testing.assert_array_equal(resumed["trace_state"],
                                  recorded["trace_state"])
    # store round-trip (f32 JSON) reproduces the buffers bit-for-bit —
    # epoch-indexed buffers have no slack, so no compaction applies
    hit = run_point(pt, backend="vmap", store=store)
    for k in ("trace_state", "trace_state_sys", "trace_state_epochs"):
        np.testing.assert_array_equal(hit[k], recorded[k], err_msg=k)


def _bench_bytes(path, res):
    write_bench_json(path, "sweep:cmp", build_report(res))
    with open(path, "rb") as f:
        return f.read()


def test_sigkilled_state_dispatch_resumes_to_identical_report(tmp_path):
    """A state-traced sweep whose worker is SIGKILL'd mid-run redispatches
    to a BENCH report byte-identical to an uninterrupted single-process
    run — φ-convergence and heatmap indices included."""
    ref = _bench_bytes(str(tmp_path / "ref.json"), execute(SPEC_KILL))
    assert b"phi_residual_curve" in ref
    assert b"queue_depth_heatmap" in ref
    store = ResultStore(str(tmp_path / "cache"))
    prog = str(tmp_path / "progress.jsonl")
    (proc,) = spawn_workers(SPEC_KILL, store.root, 1, lease_ttl_s=2.0,
                            progress_path=prog)
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if any(r.get("event") == "point"
                   for r in read_progress(prog)):
                break
            assert proc.is_alive(), "worker died before first point"
            time.sleep(0.05)
        else:
            pytest.fail("worker produced no point within 300s")
        proc.kill()
    finally:
        proc.join()
    with pytest.raises(RuntimeError, match="redispatch to resume"):
        collect(SPEC_KILL, store)
    res = dispatch(SPEC_KILL, store, workers=2, lease_ttl_s=2.0,
                   progress_path=prog)
    assert _bench_bytes(str(tmp_path / "resumed.json"), res) == ref
    # workers surfaced live gauges while computing
    assert any(r.get("event") == "gauges" and "queue_depth_mean" in r
               for r in read_progress(prog))


# ---------------------------------------------------------------------------
# report + export surfaces
# ---------------------------------------------------------------------------


def test_report_carries_state_indices(recorded, plain, sdec):
    doc = build_report({"pt": recorded})["points"]["pt"]
    assert "trace_state" not in doc         # buffers aggregated, not dumped
    assert doc["state_sample_count"] == N_EPOCHS
    assert doc["state_nodes"] == N
    curve = doc["phi_residual_curve"]
    assert len(curve) == N_EPOCHS and curve[-1] == 0.0
    assert doc["queue_jain_final"] == pytest.approx(
        float(sdec["queue_jain"][:, -1].mean()), rel=1e-4)
    heat = np.asarray(doc["queue_depth_heatmap"])
    assert heat.shape == (N_EPOCHS, N)      # < 128 epochs: no downsampling
    assert doc["completion_rate_final"] > 0
    # unrecorded points keep the historical shape: no state section at all
    doc0 = build_report({"pt": plain})["points"]["pt"]
    assert not any(k.startswith("state_") or k.startswith("phi_")
                   for k in doc0)


def test_state_counter_track_export(tmp_path, sdec):
    path = write_chrome_trace(str(tmp_path / "t.json"),
                              {k: np.zeros((0,)) for k in
                               ("seq", "src", "dst", "created_t",
                                "completed_t", "latency_s", "exit_label",
                                "layers", "hops", "is_dropped")},
                              state=sdec)
    with open(path) as f:
        doc = json.load(f)                  # validates as JSON
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert counters, "no counter events emitted"
    assert all(e["pid"] == 1 and "args" in e and e["ts"] >= 0
               for e in counters)
    names = {e["name"] for e in counters}
    assert "swarm queue depth" in names and "swarm phi" in names
    assert any(n.startswith("uav ") and n.endswith(" phi") for n in names)
    # counter samples: one per gauge lane per valid epoch
    lane = [e for e in counters if e["name"] == "swarm queue depth"]
    assert len(lane) == N_EPOCHS
    assert set(lane[0]["args"]) == {"mean", "max"}
    assert doc["otherData"]["state_schema"] == list(schema.STATE_GAUGES)
    assert doc["otherData"]["state_sys_schema"] == list(schema.SYS_GAUGES)


def test_counter_events_standalone_without_sys():
    """Node-only decode (no sys buffer) still renders per-UAV lanes."""
    state = np.zeros((3, 2, schema.NUM_STATE_GAUGES))
    state[:, :, schema.ST_PHI] = 1.0
    ev = state_counter_events(decode_state(state))
    assert any(e["name"] == "uav 0 phi" for e in ev)
    assert not any(e["name"].startswith("swarm ") for e in ev
                   if e.get("ph") == "C")


def test_serve_stats_share_the_state_gauge_schema():
    """ServeStats.record_state rows decode through the same repro.trace
    pipeline as the simulator's flight recorder."""
    from repro.splitcompute.serve_engine import ServeStats
    st = ServeStats()
    st._generated = 4
    st.record_state(t=0.05, queue_depths=[3, 1, 0], load=[0.5, 0.2, 0.1])
    st._completed = 2
    st.record_state(t=0.10, queue_depths=[1, 1, 0], load=[0.4, 0.3, 0.1])
    assert st.state_records.shape == (2, schema.NUM_SYS_GAUGES)
    assert st.stage_state.shape == (2, 3, schema.NUM_STATE_GAUGES)
    dec = decode_state(st.stage_state, st.state_records)
    assert dec["completed"][0, -1] == 2
    assert dec["queue_depth_max"][0, 0] == 3
    np.testing.assert_allclose(dec["phi"][0, 0], [0.5, 0.2, 0.1])
    idx = state_indices(dec)
    assert idx["state_sample_count"] == 2 and idx["state_nodes"] == 3
    assert idx["queue_jain_final"] is not None
    # gauges render as counter tracks like the sim side's
    assert any(e.get("ph") == "C" for e in state_counter_events(dec))


def test_serve_engine_steps_record_state():
    """SplitServeEngine.step() samples the recorder each epoch, with the
    congestion metric D in the φ lane."""
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.splitcompute import SplitServeEngine, plan_stages
    cfg = reduced(get_config("qwen3-1.7b"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    plan = plan_stages(cfg, [400.0, 420.0])
    eng = SplitServeEngine(cfg, params, plan, tau_med=1e9, tau_high=2e9)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    eng.submit({"tokens": toks})
    eng.step()
    eng.step()
    assert eng.stats.state_records.shape[0] == 2
    dec = decode_state(eng.stats.stage_state, eng.stats.state_records)
    assert dec["generated"][0, -1] == 1
    assert dec["queue_depth"].shape == (1, 2, eng.n_stages)
    assert np.all(dec["t"][0] == [0.05, 0.10])


# ---------------------------------------------------------------------------
# compile/execute spans
# ---------------------------------------------------------------------------


def test_run_point_fills_spans_only_when_computing(tmp_path):
    spec = SweepSpec.build("spans", CFG_ST, strategies=(DISTRIBUTED,),
                           num_runs=2)
    (pt,) = spec.expand()
    store = ResultStore(str(tmp_path))
    spans = {}
    first = run_point(pt, store=store, spans=spans)
    assert spans["_compile_s"] >= 0 and spans["_execute_s"] > 0
    assert not any(k.startswith("_") for k in first)
    hit_spans = {}
    hit = run_point(pt, store=store, spans=hit_spans)
    assert hit_spans == {}                  # a cache hit cost nothing
    assert sorted(hit) == sorted(first)     # identical metric surface
