"""Work that a kernel's job needs, counted from unpadded shapes, and the
chip peaks it is held against (``bench/peaks.json``, keyed by
``device_kind``).

Padding, tiling and layout are how a kernel does the job, not the job, so
none of it is counted: a kernel that pads N = 30 to a 128-wide tile reads
the whole tile but is credited with 30 columns.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

F32 = 4
I32 = 4


def phi_update(runs: int, n: int, k: Optional[int] = None) -> Dict[str, int]:
    """One diffusive φ update (paper Eq. 10) for ``runs`` swarms of ``n``
    nodes: dense over the [N, N] link delays, or sparse over [N, K]
    neighbor lists (``k`` given).

    Bytes: the masked link delays (plus, sparse, the neighbor ids and the
    gathered 1/φ of each neighbor), 1/φ and F read once, φ' written once.
    Operations: per candidate link an add, a max and a degree count; per
    node a reciprocal, an add and a divide, and the degree's +1.
    """
    links = runs * n * (n if k is None else k)
    per_link_bytes = F32 if k is None else F32 + I32 + F32
    return {"bytes": links * per_link_bytes + 3 * F32 * runs * n,
            "flops": 3 * links + 4 * runs * n}


def peaks(device_kind: str, path: Optional[str] = None) -> Dict[str, float]:
    """The published peaks of one chip of this kind.  An unknown kind is an
    error, never a default."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def least_seconds(work: Dict[str, int], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of bytes over peak
    bandwidth and operations over peak rate."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["flops"] / peak["flops_per_s"])
