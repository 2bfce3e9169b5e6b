"""ops_per_tick (ops/tick): device ops executed in the traced window per
simulated tick (mean over chips).  At small N each op costs about the same
fixed launch time, so this count sets the time per tick and moves
sim_rate."""


def read(trace, counters):
    ticks = counters.get("ticks")
    if not ticks:
        return None
    return sum(trace.ops(d) for d in trace.devices) / len(trace.devices) \
        / ticks
