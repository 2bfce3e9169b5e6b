"""The trace reduction on a small trace recorded on a TPU v5 lite chip
(``record_trace.py``: 2 runs of the paper swarm, two 20-tick epochs per
execution, executions 1 and 2 traced from the tail of execution 0)."""
from __future__ import annotations

import json
import os

import pytest

from bench import spec, trace, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PATH = os.path.join(DATA, "swarm_tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "swarm_tiny.counters.json")) as fh:
        counters = json.load(fh)
    counters["peaks"] = work.peaks(counters.pop("device_kind"))
    return trace.load(PATH), counters


def test_fixture_is_small():
    assert os.path.getsize(PATH) < 600_000


def test_window_and_busy_time(recorded):
    t, _ = recorded
    assert list(t.devices) == [0]
    assert t.executions == [1, 2]
    assert 0 < t.busy_s(0) < t.window_s()


def test_phi_kernel_found_once_per_epoch(recorded):
    t, c = recorded
    _, calls = t.phi_s(0)
    assert calls == c["phi_calls"] == 4


def test_every_reader(recorded):
    t, c = recorded
    cell = spec.Cell("paper-dist")
    got = {m["name"]: cell.reader(m["name"]).read(t, c)
           for m in cell.per_layer()}
    assert set(got) == {"device_idle", "ops_per_tick", "phi_share",
                        "phi_roofline", "exec_gap_ms"}
    assert 0 < got["device_idle"] < 100
    assert 100 < got["ops_per_tick"] < 400
    assert 0 < got["phi_share"] < 5
    assert 0 < got["phi_roofline"] < 100
    assert 0 < got["exec_gap_ms"] < 50


def test_readers_find_nothing_without_counters(recorded):
    t, _ = recorded
    cell = spec.Cell("paper-dist")
    assert cell.reader("ops_per_tick").read(t, {}) is None
    assert cell.reader("phi_roofline").read(t, {}) is None


def test_breakdown(recorded):
    t, _ = recorded
    b = t.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for k in b:
        assert 1 <= len(b[k]) <= 10
        assert all(isinstance(n, str) and v > 0 for n, v in b[k])
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= t.busy_s(0) * 1.0001
    assert any("execution" in n for n, _ in b["idle_gaps"])


def test_reduction_is_pinned(recorded):
    """The numbers this trace reduces to, as first computed: a change to
    the reduction shows here."""
    t, c = recorded
    cell = spec.Cell("paper-dist")
    got = {m["name"]: cell.reader(m["name"]).read(t, c)
           for m in cell.per_layer()}
    assert got == pytest.approx({
        "device_idle": 56.18554564800209, "ops_per_tick": 165.325,
        "phi_share": 0.054735071160635675,
        "phi_roofline": 0.792000792000792, "exec_gap_ms": 1.8047935},
        rel=1e-9)
    assert t.window_s() == pytest.approx(0.020365382, rel=1e-9)
    assert t.busy_s(0) == pytest.approx(0.008922981, rel=1e-9)
