"""Baseline / exemption file for swarmlint (``analysis_baseline.toml``).

Two table arrays:

``[[allow]]`` — one deliberate finding, matched by (rule, file, symbol)::

    [[allow]]
    rule = "R001"
    file = "src/repro/swarm/simulator.py"
    symbol = "_epoch:key"
    reason = "scenario keys folded off the epoch key for bit-identity"

Line numbers are deliberately *not* part of the match, so baselines
survive unrelated edits; ``symbol`` is the rule's stable anchor (function-
qualified variable for R001, function qualname for R003, …).  Every entry
must carry a non-empty ``reason`` — entries without one are rejected at
load time, which is the enforcement half of the "baseline with
justification" workflow (DESIGN.md §13).

``[[digest_exempt]]`` — R002's table of deliberately digest-excluded
fields, ``field = "Class.field"`` (or ``"function.param"``) plus
``reason``.  R002 validates each entry against the live dataclass/function
and flags stale or shadowed entries, so the table cannot rot.

Parsed with the standard library's ``tomllib``.
"""
from __future__ import annotations

import dataclasses
import os
import re
import tomllib
from typing import Dict, Optional

from repro.analysis.astutil import Finding

BASELINE_NAME = "analysis_baseline.toml"

# one `key = "string"` line of an entry (the baseline keeps to that shape)
_KV = re.compile(r'^([A-Za-z_][A-Za-z0-9_]*)\s*=\s*"((?:[^"\\]|\\.)*)"\s*$')


@dataclasses.dataclass(frozen=True)
class Baseline:
    allows_: tuple     # of (rule, file, symbol)
    digest_exempt: Dict[str, str]      # field -> reason
    path: Optional[str] = None

    def allows(self, f: Finding) -> bool:
        return (f.rule, f.file, f.symbol) in self.allows_

    @property
    def count(self) -> int:
        return len(self.allows_)


def parse_baseline(text: str, path: Optional[str] = None) -> Baseline:
    doc = tomllib.loads(text)
    allows = []
    for i, entry in enumerate(doc.get("allow", [])):
        missing = {"rule", "file", "symbol", "reason"} - set(entry)
        if missing:
            raise ValueError(
                f"[[allow]] entry {i} is missing {sorted(missing)}")
        if not str(entry["reason"]).strip():
            raise ValueError(
                f"[[allow]] entry {i} ({entry['rule']} {entry['symbol']}) "
                "has an empty reason — baselines must be justified")
        allows.append((entry["rule"], entry["file"], entry["symbol"]))
    exempt: Dict[str, str] = {}
    for i, entry in enumerate(doc.get("digest_exempt", [])):
        missing = {"field", "reason"} - set(entry)
        if missing:
            raise ValueError(
                f"[[digest_exempt]] entry {i} is missing {sorted(missing)}")
        if not str(entry["reason"]).strip():
            raise ValueError(
                f"[[digest_exempt]] entry {i} ({entry['field']}) has an "
                "empty reason — exemptions must be justified")
        exempt[entry["field"]] = entry["reason"]
    return Baseline(tuple(allows), exempt, path)


def load_baseline(root: str) -> Optional[Baseline]:
    path = os.path.join(root, BASELINE_NAME)
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        return parse_baseline(f.read(), path)


# ---------------------------------------------------------------------------
# pruning (``--prune-baseline``): dead [[allow]] entries mask regressions
# ---------------------------------------------------------------------------


def prune_baseline_text(text: str, live, rules_run) -> tuple:
    """Drop every ``[[allow]]`` block whose (rule, file, symbol) matches
    no live finding.  Returns ``(new_text, dropped)`` where ``dropped``
    is the list of removed triples.

    Only entries whose rule is in ``rules_run`` are candidates — an entry
    for a rule that did not execute this invocation (e.g. a J rule under
    ``--tier ast``) cannot be proven dead and is kept.  The rewrite is
    textual and scoped to the dropped blocks (first ``[[allow]]`` line
    through the last key line before the next table header), so comments
    and ``[[digest_exempt]]`` entries survive byte-for-byte.
    """
    lines = text.splitlines(keepends=True)
    # block spans: (start, end, triple) — end exclusive
    spans = []
    i = 0
    while i < len(lines):
        if lines[i].strip() == "[[allow]]":
            start = i
            entry = {}
            i += 1
            while i < len(lines):
                s = lines[i].strip()
                if s.startswith("[["):
                    break
                m = _KV.match(s)
                if m:
                    entry[m.group(1)] = m.group(2)
                i += 1
            # trim trailing blank/comment lines back out of the block so
            # the next block's leading comments aren't swallowed
            end = i
            while end > start + 1 and not _KV.match(lines[end - 1].strip()):
                end -= 1
            spans.append((start, end,
                          (entry.get("rule", ""), entry.get("file", ""),
                           entry.get("symbol", ""))))
        else:
            i += 1
    live = set(live)
    dropped = [t for _, _, t in spans
               if t not in live and t[0] in set(rules_run)]
    keep_mask = [True] * len(lines)
    for start, end, t in spans:
        if t in dropped:
            for j in range(start, end):
                keep_mask[j] = False
            # also absorb one trailing blank line left behind
            if end < len(lines) and not lines[end].strip():
                keep_mask[end] = False
    new_text = "".join(ln for ln, keep in zip(lines, keep_mask, strict=True) if keep)
    return new_text, dropped


def prune_baseline(root: str, live, rules_run) -> list:
    """Rewrite ``analysis_baseline.toml`` in place, dropping dead
    ``[[allow]]`` entries; returns the dropped (rule, file, symbol)
    triples (empty when the file is absent or already minimal)."""
    path = os.path.join(root, BASELINE_NAME)
    if not os.path.isfile(path):
        return []
    with open(path, encoding="utf-8") as f:
        text = f.read()
    new_text, dropped = prune_baseline_text(text, live, rules_run)
    if dropped:
        parse_baseline(new_text, path)     # never write an unloadable file
        with open(path, "w", encoding="utf-8") as f:
            f.write(new_text)
    return dropped
