"""Baseline suppression: every silenced finding carries its *why*.

The baseline file (``analysis_baseline.json`` at the repo root) is a
JSON list of entries::

    [{"rule": "purity",
      "path": "src/repro/core/collab/channel.py",
      "symbol": "SimChannel.send",
      "justification": "realtime=True is an explicit opt-in demo mode"}]

An entry suppresses findings whose ``(rule, path, symbol)`` matches
exactly. Two properties are enforced, not hoped for:

* an entry without a non-empty ``justification`` string is itself a
  finding (``baseline-justification``) — the baseline documents debt,
  it does not hide it;
* an entry that matches nothing is a ``stale-suppression`` finding —
  fixed findings must leave the baseline with the fix, so the file
  never accretes dead exemptions. Staleness is only decided for entries
  whose ``path`` was actually scanned: a partial run (e.g. the CI step
  that checks ``examples/fleet_sim.py`` alone) cannot conclude an
  entry is dead for a file it never analyzed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.findings import Finding


@dataclass(frozen=True)
class BaselineEntry:
    """One justified suppression."""
    rule: str
    path: str
    symbol: str
    justification: str = ""

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.symbol)


def load_baseline(path: str) -> List[BaselineEntry]:
    """Parse a baseline file; raises ``ValueError`` on malformed docs."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, list):
        raise ValueError(f"baseline {path} must be a JSON list")
    entries = []
    for i, rec in enumerate(doc):
        try:
            entries.append(BaselineEntry(
                rule=rec["rule"], path=rec["path"], symbol=rec["symbol"],
                justification=rec.get("justification", "")))
        except (TypeError, KeyError) as e:
            raise ValueError(
                f"baseline {path} entry {i} lacks rule/path/symbol: {e}")
    return entries


def apply_baseline(findings: Sequence[Finding],
                   entries: Sequence[BaselineEntry],
                   baseline_path: str = "analysis_baseline.json",
                   scanned_paths: Optional[Set[str]] = None,
                   ) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (unsuppressed, suppressed) and append the
    baseline's own violations — unjustified entries and stale ones — to
    the unsuppressed list. ``scanned_paths`` (repo-relative) limits the
    staleness check to entries whose file this run actually analyzed;
    ``None`` means the run was complete and every entry is in scope."""
    by_key: Dict[Tuple[str, str, str], BaselineEntry] = {
        e.key: e for e in entries}
    used = set()
    unsuppressed: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        entry = by_key.get(f.key)
        if entry is not None and entry.justification.strip():
            suppressed.append(f)
            used.add(entry.key)
        else:
            unsuppressed.append(f)
    for e in entries:
        if not e.justification.strip():
            unsuppressed.append(Finding(
                "baseline-justification", baseline_path, 1,
                f"{e.rule}:{e.path}:{e.symbol}",
                "baseline entry carries no justification string — "
                "suppressed findings must say why"))
        elif e.key not in used and (scanned_paths is None
                                    or e.path in scanned_paths):
            unsuppressed.append(Finding(
                "stale-suppression", baseline_path, 1,
                f"{e.rule}:{e.path}:{e.symbol}",
                "baseline entry matches no current finding — remove it"))
    return unsuppressed, suppressed
