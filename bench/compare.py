"""The comparison that decides ``correct``.

The program's statistics and the reference's, run by run, for the same
executions.  For each statistic the gap is the largest difference over the
compared runs, as a share of the largest magnitude the reference gives that
statistic over those runs; ``stat_gap`` is the widest such gap over all
statistics.  A statistic that the reference gives as 0 in every run is held
to the absolute difference.  A missing or non-finite answer reads as
``NOTHING`` (1e30), so every reading stays a finite JSON number.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

NOTHING = 1e30


def stat_gaps(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
              ) -> Dict[str, float]:
    gaps = {}
    for k, w in want.items():
        w = np.asarray(w, np.float64)
        g = np.asarray(got[k], np.float64)
        if g.shape != w.shape:
            gaps[k] = NOTHING
            continue
        diff = np.abs(g - w)
        worst = float(np.max(diff)) if diff.size else 0.0
        scale = float(np.max(np.abs(w))) if w.size else 0.0
        gap = worst / scale if scale > 0 else worst
        gaps[k] = gap if np.isfinite(gap) else NOTHING
    return gaps


def checks(got: Optional[Dict], want: Optional[Dict],
           limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Every compared number beside its limit.  Nothing to compare (no
    execution finished) reads as ``NOTHING``."""
    gap = max(stat_gaps(got, want).values()) if got is not None \
        else NOTHING
    return {"stat_gap": {"value": gap, "limit": float(limits["stat_gap"])}}


def sample_executions(seed: int, available: Sequence[int], k: int
                      ) -> List[int]:
    """``k`` of the window's executions, drawn from the seed (all of them
    when there are no more than ``k``)."""
    available = list(available)
    if len(available) <= k:
        return available
    rng = np.random.default_rng(seed)
    return sorted(int(x) for x in rng.choice(available, k, replace=False))
