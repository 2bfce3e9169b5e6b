"""phi_share (%): device time of the diffusive φ update's ops (paper
Eq. 10: the ``diffusive_phi`` kernels, or ops under a ``phi_update`` scope)
as a share of device busy time, mean over chips.  None where the trace
holds no φ op."""


def read(trace, counters):
    shares = []
    for d in trace.devices:
        phi_s, n = trace.phi_s(d)
        busy = trace.busy_s(d)
        if n and busy > 0:
            shares.append(100.0 * phi_s / busy)
    return sum(shares) / len(shares) if shares else None
