"""The comparison that decides ``correct`` fails what it must: the control
(the reference in the next precision below) and the faults a swarm cell can
have, each planted under a whole run of the harness on the CPU at a size a
test can hold.  The same run with nothing planted is correct."""
from __future__ import annotations

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, run, spec
from bench.reference import swarm as reference

TINY = {"num_workers": 8, "num_runs": 4, "sim_time_s": 1.0}
# the sparse neighbor-list path at a size where its lists are not the whole
# swarm
SPARSE = dict(TINY, num_workers=40, neighbor_mode="sparse", area_m=60000.0,
              neighbor_k=6)
# long enough that RandomAcyclic sends a task back towards a node it has
# left, so that its visited sets change the statistics
MID = {"num_workers": 30, "num_runs": 8, "sim_time_s": 40.0}
# (test cell, configuration, traffic, settings over the configuration's,
# compared executions): one tiny cut per benchmark cell, a sparse and a
# RandomAcyclic twin, and the cells that show where the visited sets are
# read
CUTS = [(f"tiny-{w['name']}", w["config"], w["traffic"], TINY, 2)
        for w in spec.load_benchmark()["workloads"]] + [
    ("tiny-paper-sparse", "swarm-paper", "table2-dist", SPARSE, 2),
    ("tiny-paper-acyclic", "swarm-paper", "table2-acyclic", TINY, 2),
    ("mid-paper-dist", "swarm-paper", "table2-dist", MID, 1),
    ("mid-paper-acyclic", "swarm-paper", "table2-acyclic", MID, 1)]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the cells of ``CUTS`` added (their
    configurations' settings and limits, few nodes, runs and seconds)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = spec.load_benchmark()
    for name, config, traffic, small, executions in CUTS:
        conf = json.loads((root / "bench" / "configs" /
                           f"{config}.json").read_text())
        conf["swarm"].update(small)
        conf["chips"] = 1
        conf["compare"].update(executions=executions,
                               block=small["num_runs"])
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(conf))
        doc["configs"].append({"name": name, "source": "test",
                               "file": f"bench/configs/{name}.json",
                               "reduced": [], "why": "test"})
        doc["workloads"].append({"name": name, "config": name,
                                 "traffic": traffic, "chips": 1,
                                 "why": "test"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def _cpu(chips):
    return jax.devices(), None


def _run(root, cell, capsys, seconds="0.3"):
    rc = run.main(["--workload", cell, "--seed", "2147483700", "--seconds",
                   seconds, "--trace", "0"], check=_cpu, root=str(root),
                  cache=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


TINY_CELLS = [name for name, _, _, small, _ in CUTS if small is not MID]


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_sound_run_is_correct(checkout, capsys, cell):
    line = _run(checkout, cell, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["stat_gap"]["value"] == 0.0


def _batch_fault(kind):
    from repro.fleet import executor
    real = executor.run_batch
    last = {}

    def faulty(key, cfg, strategy, n, runs, **kw):
        out = {k: np.array(v) for k, v in real(key, cfg, strategy, n, runs,
                                               **kw).items()}
        if kind == "state_unchanged":      # a step hands back stale state
            out, last["out"] = last.get("out", out), out
        elif kind == "half_batch":         # half the runs left out, the
            h = runs // 2                  # rest given their mean
            for v in out.values():
                v[h:] = v[:h].mean(axis=0)
        elif kind == "no_exchange":        # every shard computes shard 0
            h = runs // 2
            for v in out.values():
                v[h:] = v[:runs - h]
        elif kind == "answer_altered":     # one answer changed where made
            out["completed"][0] += 1.0
        return {k: jnp.asarray(v) for k, v in out.items()}
    return faulty


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "answer_altered"])
@pytest.mark.parametrize("cell", TINY_CELLS)
def test_fault_is_not_correct(checkout, capsys, monkeypatch, cell, fault):
    from repro.fleet import executor
    monkeypatch.setattr(executor, "run_batch", _batch_fault(fault))
    line = _run(checkout, cell, capsys)
    assert line["correct"] is False
    c = line["checks"]["stat_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell, expected", [("mid-paper-dist", True),
                                            ("mid-paper-acyclic", False)])
def test_lost_visited_sets_fail_where_read(checkout, capsys, monkeypatch,
                                           cell, expected):
    """Tasks delivered without the set of nodes they have left: the
    RandomAcyclic cell, whose decisions read those sets, is not correct;
    under Distributed nothing reads them, and the run stays correct."""
    from repro.fleet import executor
    from repro.swarm import transfer
    real = transfer.push

    def lost(st, mask, cum, created, visited, extras=None):
        return real(st, mask, cum, created, jnp.zeros_like(visited), extras)

    monkeypatch.setattr(transfer, "push", lost)
    executor._profiled_vmap.cache_clear()
    try:
        line = _run(checkout, cell, capsys)
    finally:
        executor._profiled_vmap.cache_clear()
    assert line["failed"] == 0 and line["correct"] is expected


@pytest.mark.parametrize("name", TINY_CELLS)
def test_control_is_not_correct(checkout, name):
    """The reference in bfloat16 in the program's place fails the cell's
    limit, at a tiny size of the same configuration."""
    cell = spec.Cell(name, spec.load_benchmark(str(checkout)),
                     str(checkout / "bench"))
    runner = cell.runner()
    s = runner.settings(cell.config, cell.traffic)
    keys = runner.execution_keys(7, [0, 1], s["num_runs"])
    strategy = cell.traffic["strategy"]
    want = reference.run_keys(keys, s, strategy)
    got = reference.run_keys(keys, s, strategy, jnp.bfloat16)
    c = compare.checks(got, want, cell.config["compare"]["limits"])
    assert c["stat_gap"]["value"] > c["stat_gap"]["limit"]
