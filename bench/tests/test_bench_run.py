"""bench/run.py refuses to measure without a TPU, and a run's work
accounting holds for a tiny configuration driven on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import run, spec

ROOT = spec.ROOT


def _no_result(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return not lines or not lines[-1].startswith("{")


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "paper-dist", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ has no program."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "paper-dist", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=tmp_path)
    assert proc.returncode != 0 and _no_result(proc)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = spec.load_benchmark()
    conf = json.loads((root / "bench" / "configs" /
                       "swarm-paper.json").read_text())
    conf["swarm"].update(num_workers=6, num_runs=3, sim_time_s=0.6)
    conf["compare"].update(executions=3, block=3)
    (root / "bench" / "configs" / "swarm-tiny.json").write_text(
        json.dumps(conf))
    doc["configs"].append(dict(doc["configs"][0], name="swarm-tiny",
                               file="bench/configs/swarm-tiny.json"))
    doc["workloads"].append({"name": "tiny", "config": "swarm-tiny",
                             "traffic": "table2-dist", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_work_accounting(tiny_root, capsys):
    rc = run.main(["--workload", "tiny", "--seed", "3000000001",
                   "--seconds", "0.5", "--trace", "0"],
                  check=lambda chips: (jax.devices(), None),
                  root=str(tiny_root), cache=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    setup = json.loads(next(ln for ln in out if ln.startswith("setup "))[6:])
    n = setup["executions"]
    assert line["attempted"] == n == len(setup["execution_s"]) >= 1
    assert line["failed"] == 0 and line["correct"] is True
    # whole executions only: 3 runs x 0.6 simulated s each, over the time
    # from the window's start to the end of its last execution
    rate = line["metrics"]["sim_rate"]["value"]
    assert rate == pytest.approx(n * 3 * 0.6 / setup["window_s"], rel=1e-12)
    assert setup["window_s"] >= 0.5
    assert setup["window_s"] >= sum(setup["execution_s"]) * 0.999
    assert setup["compiles_in_window"]["compiles"] == 0
    assert line["metrics"]["setup_s"]["value"] == setup["setup_s"] > 0
    assert set(line["metrics"]) == {"sim_rate", "peak_hbm", "setup_s"}
    assert line["device"]["count"] == len(jax.devices())
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert len(setup["compared_executions"]) == min(3, n)
