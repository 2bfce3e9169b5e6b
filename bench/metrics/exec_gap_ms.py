"""exec_gap_ms (ms): the device's idle time between one execution and the
next (from the end of the work before a dispatch to the start of the
dispatched program), mean over the traced executions and chips.  This is
the executor's and the harness's host time between programs; it moves
sim_rate."""


def read(trace, counters):
    gaps = [g for d in trace.devices for g in trace.execution_gaps_s(d)]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
