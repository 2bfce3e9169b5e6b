"""Reduction of a JAX profiler trace (``.xplane.pb``) to device intervals.

What a TPU trace holds, as read from one by hand (TPU v5 lite, jax 0.9):

* a plane ``/device:TPU:<i>`` per chip, whose line ``XLA Ops`` has one event
  per executed HLO instruction, named by the instruction's text
  (``%fusion.780 = pred[192000,30]{...} fusion(...), kind=kCustom, ...``) -
  inside a loop, one event per iteration; a ``while`` event spans its whole
  loop, body ops included - and whose line ``XLA Modules`` has one event per
  program run (``jit_fn(<fingerprint>)``);
* the host plane ``/host:CPU``, whose threads carry the harness's own
  ``TraceAnnotation`` spans (``dispatch``, ``block``, ``collect``, each with
  its ``execution`` index);
* one time base for both, in nanoseconds from the start of the trace.

Busy time is the union of the intervals of device ops that are not
containers (``while``, ``conditional``, ``call``): a container's interval
covers the gaps between its body's ops, which are idle time.

The traced window starts where the device finished the execution before the
first traced one (the harness starts the trace during that execution's
tail) and ends with the last device op.
"""
from __future__ import annotations

import gzip
import re
from array import array
from typing import Dict, List, Optional, Tuple

import numpy as np

SPANS = ("dispatch", "block", "collect")
CONTAINER = re.compile(r"[\]\}\)] (while|conditional|call)\(")
OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9-]*)\(")
SHAPE = re.compile(r"^%\S+ = (\(?[a-z0-9]+\[[0-9,]*\])")
PHI = ("diffusive_phi", "phi_update")


def _label(text: str) -> str:
    """A short, readable name of an HLO instruction's text."""
    head = text.split(" = ", 1)[0].lstrip("%")
    op = OPCODE.search(text)
    shape = SHAPE.match(text)
    kind = re.search(r"kind=(k\w+)", text)
    parts = [head, op.group(1) if op else "",
             shape.group(1).lstrip("(") if shape else "",
             kind.group(1) if kind else ""]
    return " ".join(p for p in parts if p)


class Device:
    """One chip's op and module intervals (ns, sorted by start)."""

    def __init__(self, ops, names, modules):
        start, dur, ids = ops
        order = np.argsort(start, kind="stable")
        self.start = np.asarray(start)[order]
        self.end = self.start + np.asarray(dur)[order]
        self.name_id = np.asarray(ids, dtype=np.int64)[order]
        self.names = names
        self.container = np.array([bool(CONTAINER.search(n)) for n in names],
                                  bool)
        self.phi = np.array([any(p in n for p in PHI) for n in names], bool)
        self.modules = sorted(modules, key=lambda m: m[1])

    def leaf(self, lo: float, hi: float) -> np.ndarray:
        """Mask of the non-container ops that start inside [lo, hi)."""
        return (~self.container[self.name_id]) & (self.start >= lo) & \
            (self.start < hi)

    def intervals(self, lo: float, hi: float) -> np.ndarray:
        """Merged busy intervals of leaf ops, clipped to [lo, hi]."""
        m = (~self.container[self.name_id]) & (self.end > lo) & \
            (self.start < hi)
        s = np.clip(self.start[m], lo, hi)
        e = np.clip(self.end[m], lo, hi)
        if not len(s):
            return np.zeros((0, 2))
        order = np.argsort(s, kind="stable")
        s, e = s[order], e[order]
        run_end = np.maximum.accumulate(e)
        new = np.concatenate([[True], s[1:] > run_end[:-1]])
        idx = np.flatnonzero(new)
        ends = np.append(run_end[idx[1:] - 1], run_end[-1])
        return np.stack([s[idx], ends], axis=1)

    def busy_ns(self, lo: float, hi: float) -> float:
        iv = self.intervals(lo, hi)
        return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0

    def gaps(self, lo: float, hi: float) -> np.ndarray:
        """Idle intervals inside [lo, hi]."""
        iv = self.intervals(lo, hi)
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        return edges[edges[:, 1] > edges[:, 0]]

    def last_end_before(self, t: float) -> Optional[float]:
        m = (~self.container[self.name_id]) & (self.end <= t)
        return float(np.max(self.end[m])) if m.any() else None

    def main_module(self) -> Optional[str]:
        """The program that took most device time: the executions'."""
        tot: Dict[str, float] = {}
        for name, s, e in self.modules:
            tot[name] = tot.get(name, 0.0) + (e - s)
        return max(tot, key=tot.get) if tot else None


class Trace:
    """Devices, host spans and the traced window of one trace file."""

    def __init__(self, devices: Dict[int, Device], spans: List[Tuple]):
        self.devices = devices
        self.spans = sorted(spans, key=lambda s: s[1])
        dispatch = [s for s in self.spans if s[0] == "dispatch"]
        self.executions = [s[3] for s in dispatch]
        first = dispatch[0][1] if dispatch else 0.0
        ends = [d.end[~d.container[d.name_id]] for d in devices.values()]
        self.hi = max(float(np.max(e)) for e in ends if len(e))
        before = [d.last_end_before(first) for d in devices.values()]
        before = [b for b in before if b is not None]
        self.lo = min(before) if before else first

    # --- window and busy time (seconds) ---------------------------------
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, dev: int) -> float:
        return self.devices[dev].busy_ns(self.lo, self.hi) * 1e-9

    def ops(self, dev: int) -> int:
        return int(np.sum(self.devices[dev].leaf(self.lo, self.hi)))

    def phi_s(self, dev: int) -> Tuple[float, int]:
        """Device seconds and count of the φ update's ops."""
        d = self.devices[dev]
        m = d.leaf(self.lo, self.hi) & d.phi[d.name_id]
        return float(np.sum(d.end[m] - d.start[m])) * 1e-9, int(m.sum())

    def execution_gaps_s(self, dev: int) -> List[float]:
        """For each traced execution, the device's idle time between the end
        of the work before its dispatch and the start of its program."""
        d = self.devices[dev]
        main = d.main_module()
        starts = [s for name, s, _ in d.modules if name == main]
        out = []
        for name, t, _, _ in self.spans:
            if name != "dispatch":
                continue
            lo = d.last_end_before(t)
            nxt = [s for s in starts if s >= t]
            if lo is None or not nxt:
                continue
            gaps = d.gaps(lo, nxt[0])
            out.append(float(np.sum(gaps[:, 1] - gaps[:, 0])) * 1e-9)
        return out

    def host_span_at(self, t: float) -> str:
        for name, s, e, i in self.spans:
            if s <= t < e:
                return f"{name} (execution {i})"
        return "no harness span"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device ops that took most time (mean seconds per chip) and
        the longest idle gaps, named by what the host was doing."""
        tot: Dict[str, float] = {}
        gaps = []
        for dev, d in self.devices.items():
            m = d.leaf(self.lo, self.hi)
            sums = np.bincount(d.name_id[m], weights=(d.end - d.start)[m],
                               minlength=len(d.names))
            for i in np.flatnonzero(sums):
                label = _label(d.names[i])
                tot[label] = tot.get(label, 0.0) + sums[i] * 1e-9 / len(
                    self.devices)
            g = d.gaps(self.lo, self.hi)
            for k in np.argsort(g[:, 0] - g[:, 1])[:top]:
                s, e = g[k]
                gaps.append((f"{self.host_span_at((s + e) / 2)}"
                             + (f" on chip {dev}" if len(self.devices) > 1
                                else ""), float(e - s) * 1e-9))
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(gaps, key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def load(path: str) -> Trace:
    """A trace from an ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            start, dur, ids = array("d"), array("d"), array("l")
            index: Dict[str, int] = {}
            modules = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        start.append(e.start_ns)
                        dur.append(e.duration_ns)
                        ids.append(index.setdefault(e.name, len(index)))
                elif line.name == "XLA Modules":
                    modules += [(e.name.split("(")[0], e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
            names = sorted(index, key=index.get)
            devices[int(m.group(1))] = Device((start, dur, ids), names,
                                              modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        stats = dict(e.stats)
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      int(stats.get("execution", -1))))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    return Trace(devices, spans)
