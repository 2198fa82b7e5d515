"""Device time of one execution of the program's edge or cloud half, from a
reduced trace (``trace.TraceSummary``).

The program names each jitted half after its side and split
(``jit_edge_c13``, ``jit_cloud_c13_rows`` for the row-mapped batched
variant), and the trace names each operation by its module and HLO
instruction: ``jit_cloud_c13_rows(<fingerprint>)/%while``. A module's
operations run one at a time, except that a loop's ``%while`` spans the
operations of its body, which the trace lists too. So per module (one
fingerprint: one compiled shape) the operation with the most seconds is
never inside a loop (a ``%while`` holds at least its body's time), and its
count of events is the module's executions. The operations seen no more
often than that, give or take the two executions the window's ends may cut,
run once per execution: their seconds, among them the whole ``%while``, are
the module's device time without counting a body twice. An operation seen
more often belongs to a loop's body and is left out. A program named
otherwise (before the halves had names, ``jit_traced``) reads as absent.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional, Tuple


def half_modules(summary, half: str) -> Dict[str, Dict[str, Tuple[float, int]]]:
    """Per module of ``half`` (``"edge"`` or ``"cloud"``), each operation's
    (seconds inside the window, events)."""
    pat = re.compile(rf"jit_{half}_c\d+(_rows)?\(.*\)")
    mods: Dict[str, Dict[str, Tuple[float, int]]] = defaultdict(dict)
    for name, secs in summary.op_seconds.items():
        mod, _, op = name.partition("/")
        if pat.fullmatch(mod):
            mods[mod][op] = (secs, len(summary.op_events[name]))
    return dict(mods)


def per_execution_ms(summary, half: str) -> Optional[float]:
    """Device milliseconds of one execution of ``half``'s programs, over
    every module of it in the window; None where the trace has none."""
    if summary is None:
        return None
    seconds, runs = 0.0, 0
    for ops in half_modules(summary, half).values():
        _, n = max(ops.values())
        seconds += sum(s for s, count in ops.values() if count <= n + 2)
        runs += n
    return 1e3 * seconds / runs if runs else None
