"""device_idle (%): the share of the traced window in which no device op
ran, for the idlest chip.  Moves sim_rate: idle time is time the swarm does
not advance."""


def read(trace, counters):
    w = trace.hi - trace.lo
    if w <= 0:
        return None
    return max(100.0 * (1.0 - trace.busy_s(d) / trace.window_s())
               for d in trace.devices)
