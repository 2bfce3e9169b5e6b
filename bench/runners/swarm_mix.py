"""Runner for ``kind: "swarm_mix"`` configurations: the swarm simulator with
a task mix (``task_profiles``, ``task_mix``), driven exactly as the
``swarm`` runner drives it (``bench/runners/swarm.py``, loaded here as a
private copy), and compared with the mix's own plain reference
(``bench/reference/swarm_mix.py``): the 14 swarm statistics and the
completions of each profile.

With ``--trace 1`` it also hands the per-layer readers
``counters["op_scopes"]``: the simulator phase of each instruction of the
executable the window ran (``repro.fleet.executor.op_scopes``), which
``bench/metrics/profile_share.py`` reads.
"""
from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace
from typing import Dict

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.dirname(BENCH) not in sys.path:
    sys.path.insert(0, os.path.dirname(BENCH))

from bench.reference import swarm_mix as reference  # noqa: E402


def _swarm_runner():
    """A copy of the ``swarm`` runner module of its own, whose module-level
    reference this runner can point at the mix's."""
    path = os.path.join(BENCH, "runners", "swarm.py")
    spec = importlib.util.spec_from_file_location(
        "bench_runners_swarm_mix_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _swarm_runner()
settings = _base.settings
build_config = _base.build_config
execution_keys = _base.execution_keys


def run(ctx: Dict) -> Dict:
    cell = ctx["cell"]
    s = settings(cell.config, cell.traffic)
    _base.reference = SimpleNamespace(STATS=reference.stats_of(s),
                                      STRATEGIES=reference.STRATEGIES)
    _base.reference_for = reference_for
    result = _base.run(ctx)
    if "counters" in result:
        from repro.fleet.executor import op_scopes
        cfg = build_config(s)
        result["counters"]["op_scopes"] = op_scopes(
            cfg, cfg.num_workers, cfg.num_runs, cell.config["backend"])
    return result


def reference_for(cell, seed: int, indices, dtype=None):
    """The mix reference's statistics for executions ``indices`` of a run
    with ``seed`` (float32 unless ``dtype`` is given: the control)."""
    import jax.numpy as jnp
    s = settings(cell.config, cell.traffic)
    return reference.run_blocks(
        execution_keys(seed, indices, s["num_runs"]), s,
        cell.traffic["strategy"], int(cell.config["compare"]["block"]),
        dtype or jnp.float32)
