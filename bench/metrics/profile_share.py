"""profile_share (%): device time of the per-task profile lookups under a
task mix (ops of the main program whose simulator phase, as
``counters["op_scopes"]`` maps instruction names, is ``task_profile``) as a
share of device busy time, mean over chips.  None without ``op_scopes``, or
where the trace holds no such op (a one-profile program has none)."""
from __future__ import annotations

import numpy as np

PHASE = "task_profile"


def _instruction(text: str) -> str:
    """``fusion.780`` of ``%fusion.780 = pred[...] fusion(...), ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(trace, counters):
    scopes = counters.get("op_scopes")
    if not scopes:
        return None
    shares = []
    for dev, d in trace.devices.items():
        main = d.main_module()
        runs = np.array([(s, e) for name, s, e in d.modules if name == main],
                        np.float64).reshape(-1, 2)
        if not len(runs):
            continue
        in_phase = np.array([scopes.get(_instruction(t)) == PHASE
                             for t in d.names], bool)
        m = d.leaf(trace.lo, trace.hi) & in_phase[d.name_id]
        k = np.searchsorted(runs[:, 0], d.start, side="right") - 1
        inside = (k >= 0) & (d.start < runs[np.maximum(k, 0), 1])
        m &= inside
        busy = trace.busy_s(dev)
        if m.any() and busy > 0:
            shares.append(100.0 * float(np.sum(d.end[m] - d.start[m]))
                          * 1e-9 / busy)
    return sum(shares) / len(shares) if shares else None
