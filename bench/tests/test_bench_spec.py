"""BENCHMARK.json against the benchmark's contract, and discovery of every
piece by file name (no registry lists them)."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from bench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
BENCHMARK = spec.load_benchmark()


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCHMARK["paths"]) <= 16
    for p in BENCHMARK["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = BENCHMARK["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        assert not w.startswith("/") and ".." not in w
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in BENCHMARK["paths"])
    assert isinstance(BENCHMARK["run_seconds"], int)
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines():
    names = {"configs": set(), "workloads": set(), "metrics": set()}
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and \
            _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in BENCHMARK["paths"])
        names["configs"].add(c["name"])
    assert len(names["configs"]) == len(BENCHMARK["configs"])
    pairs = set()
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k])
        assert w["config"] in names["configs"] and w["chips"] in (1, 4)
        assert _line(w["why"])
        pairs.add((w["config"], w["traffic"]))
        names["workloads"].add(w["name"])
    assert len(pairs) == len(names["workloads"]) == len(BENCHMARK["workloads"])
    four = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert four <= max(1, len(BENCHMARK["workloads"]) // 2)
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == names["configs"]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names["metrics"]
        names["metrics"].add(m["name"])
        for c in m.get("workloads", []):
            assert c in names["workloads"]
    for m in BENCHMARK["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_resolves_by_file_name(name):
    cell = spec.Cell(name)
    conf = cell.config
    listed = {c["name"]: c for c in BENCHMARK["configs"]}[
        cell.workload["config"]]
    assert os.path.samefile(
        os.path.join(ROOT, listed["file"]),
        os.path.join(spec.BENCH, "configs", cell.workload["config"] + ".json"))
    assert conf["chips"] == cell.chips
    assert sorted(conf["reduced"]) == sorted(listed["reduced"])
    for k in ("source", "assumed", "kind"):
        assert conf[k]
    assert callable(cell.runner().run)
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = cell.per_layer()
    assert layers
    for m in layers:
        assert callable(cell.reader(m["name"]).read)


def test_swarm_configs_build(monkeypatch):
    from repro.configs import SwarmConfig
    for w in BENCHMARK["workloads"]:
        cell = spec.Cell(w["name"])
        runner = cell.runner()
        s = runner.settings(cell.config, cell.traffic)
        cfg = runner.build_config(s)
        assert isinstance(cfg, SwarmConfig)
        assert cfg.num_runs % cell.chips == 0
        assert cell.config["backend"] == ("sharded" if cell.chips > 1
                                          else "vmap")


def test_cell_added_as_files_only(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a runner kind
    added as new files (and entries in BENCHMARK.json) are found."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "bench"
    doc = json.loads(json.dumps(BENCHMARK))
    (bench / "configs" / "echo-model.json").write_text(json.dumps(
        {"kind": "echo", "source": "https://example.org/echo",
         "reduced": {}, "assumed": {"x": "y"}, "chips": 1}))
    (bench / "traffic" / "flat.json").write_text(json.dumps({"rate": 1}))
    (bench / "runners" / "echo.py").write_text(
        "def run(ctx):\n    return {'kind': 'echo'}\n")
    (bench / "metrics" / "echo_share.py").write_text(
        "def read(trace, counters):\n    return 42.0\n")
    doc["configs"].append({"name": "echo-model", "source": "https://x.org",
                           "file": "bench/configs/echo-model.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "echo-flat", "config": "echo-model",
                             "traffic": "flat", "chips": 1, "why": "test"})
    doc["end_to_end"].append({"name": "echo_rate", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["echo-flat"]})
    doc["per_layer"].append({"name": "echo_share", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "echo", "moves": "echo_rate"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = spec.Cell("echo-flat", spec.load_benchmark(str(root)), str(bench))
    assert cell.runner().run({}) == {"kind": "echo"}
    assert cell.traffic == {"rate": 1}
    assert [m["name"] for m in cell.per_layer()] == ["echo_share"]
    assert cell.reader("echo_share").read(None, {}) == 42.0
    assert {m["name"] for m in cell.end_to_end()} >= {"echo_rate", "setup_s"}
    # the cells already there do not take up the new cell's metrics
    old = spec.Cell(BENCHMARK["workloads"][0]["name"],
                    spec.load_benchmark(str(root)), str(bench))
    assert "echo_share" not in {m["name"] for m in old.per_layer()}
    assert "echo_rate" not in {m["name"] for m in old.end_to_end()}
