"""Names the program gives its work on a profiler trace, and the map from a
compiled executable's instructions to them.

Two layers carry names:

* **Phases of the simulator scan** — ``jax.named_scope`` names (``PHASES``)
  around each phase of ``run_sim``'s epoch and tick bodies.  They are
  metadata only: XLA keeps them in each instruction's
  ``metadata={op_name=".../<scope>/..."}`` and the compiled program runs
  the same ops.  A device trace names its ops by instruction text, not by
  name stack, so ``op_scopes`` reads the phases back from the compiled
  executable's HLO text (``fleet.executor.op_scopes``).
* **Host spans of the executor** — ``jax.profiler`` TraceMe annotations
  (``SPANS``) around ``run_batch``'s dispatch (a step annotation numbered
  by the process-wide execution count) and around each real compile.
  They sit on the host planes of a trace, on the device planes' clock.

An op's phase is the innermost ``PHASES`` name on its ``op_name``: the
``visited`` gather inside ``decision`` belongs to ``visited``
(``leaf_instructions`` says how ops without one are placed).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import jax

# set-up before the scan, the epoch and tick keys, the epoch level, the
# tick level, then the phases both levels share
PHASES = ("init", "keys",
          "faults", "mobility", "channel", "neighbors", "phi_update",
          "decision", "early_exit", "initiate",
          "arrivals", "compute", "transfers", "queues",
          "visited", "task_profile", "trace_capture", "summarize")

RUN_BATCH = "run_batch"     # StepTraceAnnotation, step_num = execution
COMPILE = "compile"         # TraceAnnotation, only on an executable miss
SPANS = (RUN_BATCH, COMPILE)

# instructions that run no work on the device of their own: the operands,
# tuples and views of a computation, and the containers whose bodies' ops
# are the work
STRUCTURAL = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "while", "conditional", "call", "after-all", "partition-id",
    "replica-id", "opt-barrier"})
# a custom call that only reserves a buffer
ALLOCATE = 'custom_call_target="AllocateBuffer"'

_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = .*?[\]\}\)] ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.-]+)")
_FUSED = re.compile(r"\bcalls=%([\w.-]+)")
_CALLED = re.compile(r"\b(?:to_apply|calls)=%([\w.-]+)")
_WORD = re.compile(r"[A-Za-z_]\w*")
# computations an instruction runs as ops of their own (loop bodies and
# conditions, branches, called computations), unlike ``calls=`` of a fusion
# or ``to_apply=`` of a reduction, whose instructions run inside one op
_RUNS = re.compile(r"\b(?:body|condition|true_computation|"
                   r"false_computation)=%([\w.-]+)|branch_computations="
                   r"\{([^}]*)\}")


def phase(name: str):
    """``jax.named_scope`` of one simulator phase (a name from ``PHASES``)."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
    return jax.named_scope(name)


def phase_of(op_name: str) -> Optional[str]:
    """The innermost phase on an instruction's ``op_name``, if any.  A scope
    entered under a transformation reads ``vmap(<scope>)`` there."""
    for word in reversed(_WORD.findall(op_name)):
        if word in PHASES:
            return word
    return None


class _Inst:
    __slots__ = ("name", "opcode", "structural", "op_name", "operands",
                 "fused", "runs")

    def __init__(self, line: str):
        m = _INSTRUCTION.match(line)
        self.name, self.opcode = m.groups()
        self.structural = self.opcode in STRUCTURAL or ALLOCATE in line
        op = _OP_NAME.search(line)
        self.op_name = op.group(1) if op else None
        args = line[m.end():]
        self.operands = _OPERAND.findall(args.split("), ", 1)[0])
        # computations fused into this op, or run by it as ops of their own
        self.fused = _FUSED.findall(line) if self.opcode == "fusion" else []
        self.runs = _CALLED.findall(line) \
            if self.opcode in ("call", "async-start") else []
        for a, b in _RUNS.findall(line):
            self.runs += [a] if a else [c.strip().lstrip("%")
                                        for c in b.split(",")]


def _computations(hlo_text: str) -> Tuple[Dict[str, List[_Inst]], str]:
    """The instructions of each computation, and the entry's name."""
    comps: Dict[str, List[_Inst]] = {}
    name, entry = None, None
    for line in hlo_text.splitlines():
        if name is None:
            m = _COMPUTATION.match(line)
            if m:
                name = m.group(2)
                comps[name] = []
                entry = name if m.group(1) else entry
        elif line == "}":
            name = None
        elif _INSTRUCTION.match(line):
            comps[name].append(_Inst(line))
    return comps, entry


def leaf_instructions(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> phase (None outside every phase) of each
    instruction that runs as an op of its own: those of the entry
    computation and of every loop body, loop condition, branch and called
    computation reachable from it, less the structural ones.

    An instruction takes the innermost phase on its ``op_name``; a fusion
    without one that of the instructions it fuses (the TPU compiler's
    rewrites, such as a scatter's sort and update fusions, carry metadata
    only inside); then the phase of the loop, branch or call whose body
    holds it.  What the compiler adds without any ``op_name`` (copies of a
    loop carry, reshapes around a rewritten scatter) takes the phase of
    the ops that read it, else of those it reads."""
    comps, entry = _computations(hlo_text)
    fused: Dict[str, Optional[str]] = {}

    def own(inst: _Inst) -> Optional[str]:
        if inst.op_name and phase_of(inst.op_name):
            return phase_of(inst.op_name)
        for comp in inst.fused:
            if comp not in fused:
                fused[comp] = None          # guards a cycle
                fused[comp] = _most_common(map(own, comps.get(comp, ())))
            if fused[comp]:
                return fused[comp]
        return None

    phases: Dict[str, Optional[str]] = {}
    insts: Dict[str, _Inst] = {}
    todo, seen = [(entry, None)], {entry}
    while todo:
        comp, outer = todo.pop()
        for inst in comps.get(comp, ()):
            p = own(inst) or outer
            for c in inst.runs:
                if c not in seen:
                    seen.add(c)
                    todo.append((c, p))
            if not inst.structural:
                phases[inst.name] = p
                insts[inst.name] = inst
    # what the compiler added without metadata: by data flow, readers
    # first, to a fixed point
    users: Dict[str, List[str]] = {}
    for inst in insts.values():
        for o in inst.operands:
            users.setdefault(o, []).append(inst.name)
    changed = True
    while changed:
        changed = False
        for name, inst in insts.items():
            if phases[name] or inst.op_name:
                continue
            for near in (users.get(name, ()), inst.operands):
                p = _most_common(phases.get(o) for o in near)
                if p:
                    phases[name] = p
                    changed = True
                    break
    return phases


def _most_common(phases) -> Optional[str]:
    votes: Dict[str, int] = {}
    for p in phases:
        if p:
            votes[p] = votes.get(p, 0) + 1
    return max(votes, key=votes.get) if votes else None


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name (``fusion.780``) -> phase, for every op-running
    instruction of a compiled module's HLO text that lies in a phase."""
    return {inst: p for inst, p in leaf_instructions(hlo_text).items()
            if p is not None}
