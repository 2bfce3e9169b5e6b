"""The task-mix cell (``kind: "swarm_mix"``) and the RandomAcyclic cell:
both resolve by file name, the mix's control and a planted fault fail
``correct`` at a test's size while the sound run passes, and the
``profile_share`` reader finds the ``task_profile`` ops of a trace."""
from __future__ import annotations

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, run, spec, trace
from bench.reference import swarm_mix

# long enough that Distributed offloads tasks of both networks
SMALL = {"num_workers": 8, "num_runs": 4, "sim_time_s": 10.0}
NAME = "small-mix-dist"


def test_mix_cell_resolves():
    cell = spec.Cell("mix-dist")
    assert cell.config["kind"] == "swarm_mix" and cell.chips == 1
    runner = cell.runner()
    s = runner.settings(cell.config, cell.traffic)
    cfg = runner.build_config(s)
    assert cfg.task_profiles == ("vgg16", "resnet50")
    assert cfg.task_mix == (0.5, 0.5) and not cfg.early_exit_enabled
    assert (cfg.num_workers, cfg.num_runs, cfg.sim_time_s) == (30, 50, 100.0)
    assert cell.traffic["strategy"] == "Distributed"
    assert {m["name"] for m in cell.per_layer()} == {
        "device_idle", "ops_per_tick", "phi_share", "phi_roofline",
        "exec_gap_ms", "profile_share"}
    assert {m["name"] for m in cell.end_to_end()} == {"sim_rate", "peak_hbm",
                                                      "setup_s"}
    assert swarm_mix.stats_of(s)[-2:] == ("completed_vgg16",
                                          "completed_resnet50")


def test_acyclic_cell_resolves():
    cell = spec.Cell("paper-acyclic")
    assert cell.config["kind"] == "swarm" and cell.chips == 1
    assert cell.workload["config"] == "swarm-paper"
    assert cell.traffic["strategy"] == "RandomAcyclic"
    assert "profile_share" not in {m["name"] for m in cell.per_layer()}
    assert "sim_rate" in {m["name"] for m in cell.end_to_end()}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with a small cut of the mix cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = spec.load_benchmark()
    conf = json.loads((root / "bench" / "configs" /
                       "swarm-mix-vgg16-resnet50.json").read_text())
    conf["swarm"].update(SMALL)
    conf["compare"].update(executions=2, block=SMALL["num_runs"])
    (root / "bench" / "configs" / f"{NAME}.json").write_text(json.dumps(conf))
    doc["configs"].append({"name": NAME, "source": "test",
                           "file": f"bench/configs/{NAME}.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": NAME, "config": NAME,
                             "traffic": "table2-mix-dist", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(NAME)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def _flip_profile_on_delivery(transfer):
    real = transfer.push

    def flipped(st, mask, cum, created, visited, extras=None):
        if extras and "profile" in extras:
            extras = dict(extras, profile=1 - extras["profile"])
        return real(st, mask, cum, created, visited, extras)
    return flipped


@pytest.mark.parametrize("fault", [None, "profile_flipped"])
def test_mix_run_is_correct_unless_tasks_change_network(checkout, capsys,
                                                       monkeypatch, fault):
    """A sound run agrees with the mix reference to the last bit; tasks that
    arrive at their destination as the other network do not."""
    from repro.fleet import executor
    from repro.swarm import transfer
    if fault:
        monkeypatch.setattr(transfer, "push",
                            _flip_profile_on_delivery(transfer))
    executor._profiled_vmap.cache_clear()
    try:
        rc = run.main(["--workload", NAME, "--seed", "2147483901",
                       "--seconds", "0.3", "--trace", "0"],
                      check=lambda chips: (jax.devices(), None),
                      root=str(checkout), cache=False)
    finally:
        executor._profiled_vmap.cache_clear()
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c = line["checks"]["stat_gap"]
    assert line["failed"] == 0
    if fault:
        assert line["correct"] is False and c["value"] > c["limit"]
    else:
        assert line["correct"] is True and c["value"] == 0.0


def test_mix_control_is_not_correct(checkout):
    """The mix reference in bfloat16 in the program's place fails the
    cell's limit."""
    cell = spec.Cell(NAME, spec.load_benchmark(str(checkout)),
                     str(checkout / "bench"))
    runner = cell.runner()
    want = runner.reference_for(cell, 7, [0, 1])
    got = runner.reference_for(cell, 7, [0, 1], jnp.bfloat16)
    assert set(want) == set(swarm_mix.stats_of(
        runner.settings(cell.config, cell.traffic)))
    c = compare.checks(got, want, cell.config["compare"]["limits"])
    assert c["stat_gap"]["value"] > c["stat_gap"]["limit"]


def _trace(names, starts, durs):
    dev = trace.Device((starts, durs, list(range(len(names)))), names,
                       [("jit_fn", 0.0, 1000.0), ("jit_other", 2000.0,
                                                  2600.0)])
    return trace.Trace({0: dev}, [])


def test_profile_share_reads_the_task_profile_ops():
    reader = spec.Cell("mix-dist").reader("profile_share")
    t = _trace(["%fusion.1 = s32[30]{0} fusion(%p), kind=kLoop",
                "%fusion.2 = f32[30]{0} fusion(%q), kind=kLoop",
                "%fusion.1 = s32[30]{0} fusion(%r), kind=kLoop"],
               [0.0, 100.0, 2000.0], [100.0, 300.0, 500.0])
    scopes = {"fusion.1": "task_profile", "fusion.2": "compute"}
    # fusion.1 of the main program: 100 of 900 ns busy; the other
    # program's fusion.1 is not the main program's op
    assert reader.read(t, {"op_scopes": scopes}) == pytest.approx(
        100.0 * 100.0 / 900.0)
    assert reader.read(t, {}) is None
    assert reader.read(t, {"op_scopes": {"fusion.2": "compute"}}) is None


def test_reference_statistics_are_finite(checkout):
    cell = spec.Cell(NAME, spec.load_benchmark(str(checkout)),
                     str(checkout / "bench"))
    want = cell.runner().reference_for(cell, 3, [0])
    assert all(np.all(np.isfinite(v)) for v in want.values())
    assert np.all(want["completed_vgg16"] + want["completed_resnet50"]
                  == want["completed"])
