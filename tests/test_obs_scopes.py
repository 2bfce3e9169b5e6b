"""The simulator's phase scopes and the executor's spans (repro.obs.scopes).

Phases are ``jax.named_scope`` metadata: they reach the compiled
executable's HLO text, where ``executor.op_scopes`` reads them back per
instruction.  The executor's ``run_batch`` and ``compile`` spans are
profiler TraceMe events on the host plane of a trace.
"""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SwarmConfig
from repro.fleet import executor
from repro.obs import scopes
from repro.swarm import DISTRIBUTED

BASE = dataclasses.replace(SwarmConfig(), num_workers=8, sim_time_s=1.0)
RUNS = 3
# dense at the defaults; sparse with the early exit on; every trace stream
# with Markov churn; a task mix: between them every phase has ops somewhere
CONFIGS = {
    "dense": BASE,
    "sparse": dataclasses.replace(BASE, num_workers=12,
                                  neighbor_mode="sparse", neighbor_k=4,
                                  early_exit_enabled=True),
    "traced": dataclasses.replace(BASE, fault_model="markov",
                                  trace_capacity=256,
                                  trace_hop_capacity=256,
                                  trace_state_every=2),
    "mix": dataclasses.replace(BASE, task_profiles=("vgg16", "resnet50"),
                               task_mix=(0.5, 0.5)),
}
# phases whose feature the configuration turns off, so that they run no op
# (the per-task profile lookups run only under a task mix)
OFF = {"dense": {"faults", "neighbors", "early_exit", "trace_capture",
                 "task_profile"},
       "sparse": {"faults", "trace_capture", "task_profile"},
       "traced": {"neighbors", "early_exit", "task_profile"},
       "mix": {"faults", "neighbors", "early_exit", "trace_capture"}}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_phase_that_runs_reaches_the_compiled_program(name):
    cfg = CONFIGS[name]
    found = set(executor.op_scopes(cfg, cfg.num_workers, RUNS).values())
    assert found == set(scopes.PHASES) - OFF[name]


def test_op_scopes_places_the_programs_ops():
    """Nearly every op the program emitted (one with an ``op_name``) has a
    phase; the rest are the scans' own loop counters and clocks."""
    compiled, _ = executor._executable(BASE, BASE.num_workers, RUNS, "vmap")
    text = compiled.as_text()
    named = {i.name for insts in scopes._computations(text)[0].values()
             for i in insts if i.op_name}
    leaf = scopes.leaf_instructions(text)
    ops = [k for k in leaf if k in named]
    placed = [k for k in ops if leaf[k]]
    assert len(ops) > 100
    assert len(placed) >= 0.95 * len(ops)
    assert executor.op_scopes(BASE, BASE.num_workers, RUNS) == {
        k: v for k, v in leaf.items() if v}


def test_phase_of_takes_the_innermost_scope():
    base = "jit(fn)/vmap()/while/body/closed_call"
    assert scopes.phase_of(f"{base}/decision/visited/gather") == "visited"
    assert scopes.phase_of(f"{base}/arrivals/queues/select_n") == "queues"
    assert scopes.phase_of("jit(fn)/vmap(summarize)/div") == "summarize"
    assert scopes.phase_of(f"{base}/mul") is None
    with pytest.raises(ValueError):
        scopes.phase("no_such_phase")


def test_leaf_instructions_reads_fusions_and_loops():
    text = """HloModule m

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%p), metadata={op_name="f/queues/neg"}
}

%body (t: (f32[4])) -> (f32[4]) {
  %t = (f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%t), index=0
  %c = f32[4]{0} copy(%g)
  %fusion.1 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused
  ROOT %r = (f32[4]{0}) tuple(%fusion.1)
}

%cond (t: (f32[4])) -> pred[] {
  %t = (f32[4]{0}) parameter(0)
  ROOT %k = pred[] constant(true)
}

ENTRY %main (a: f32[4]) -> (f32[4]) {
  %a = f32[4]{0} parameter(0)
  %add.2 = f32[4]{0} add(%a, %a), metadata={op_name="f/init/add"}
  %tuple.3 = (f32[4]{0}) tuple(%add.2)
  ROOT %while.4 = (f32[4]{0}) while(%tuple.3), condition=%cond, body=%body
}
"""
    # the fusion takes what it fuses; the copy takes its reader's phase
    assert scopes.leaf_instructions(text) == {
        "add.2": "init", "c": "queues", "fusion.1": "queues"}


def test_run_batch_spans_on_a_profiler_trace(tmp_path):
    """Each call is one ``run_batch`` step span, numbered by the process's
    call count; the first call's executable-cache miss is the one
    ``compile`` span, inside it."""
    cfg = dataclasses.replace(BASE, num_workers=5, sim_time_s=0.4)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            jax.block_until_ready(executor.run_batch(
                jax.random.PRNGKey(i), cfg, jnp.int32(DISTRIBUTED), 5, 2))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name in scopes.SPANS)
    steps = [e for e in events if e[2] == scopes.RUN_BATCH]
    compiles = [e for e in events if e[2] == scopes.COMPILE]
    assert len(steps) == 2 and len(compiles) == 1
    assert steps[1][3]["step_num"] == steps[0][3]["step_num"] + 1
    assert steps[0][0] <= compiles[0][0] <= compiles[0][1] <= steps[0][1]
