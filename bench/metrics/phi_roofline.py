"""phi_roofline (%): the least time the chip could take for the φ updates
of the traced window (the larger of bytes over peak bandwidth and
operations over peak rate, counted from the unpadded shapes Eq. 10 needs,
``bench/work.py``) over the device time its ops took, mean over chips.
None where the trace holds no φ op."""

from bench import work


def read(trace, counters):
    calls, phi_work = counters.get("phi_calls"), counters.get("phi_work")
    if not calls or not phi_work:
        return None
    least = calls * work.least_seconds(phi_work, counters["peaks"])
    shares = []
    for d in trace.devices:
        phi_s, n = trace.phi_s(d)
        if n and phi_s > 0:
            shares.append(100.0 * least / phi_s)
    return sum(shares) / len(shares) if shares else None
