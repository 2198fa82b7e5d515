"""Virtual-clock purity checker: no wall clock, no ambient randomness.

The fleet simulator's same-seed bit-identity contract (and ``SimChannel``
/ ``LinkTrace`` determinism) requires that nothing in the virtual-clock
domain ever reads the host clock or draws from a process-global RNG.
This checker forbids, lexically:

* ``time.time/monotonic/sleep/perf_counter/...`` (and the ``_ns``
  variants), including ``from time import ...`` of those names;
* ``datetime.now/utcnow/today`` (any ``datetime``/``date`` base);
* module-level ``random.<fn>()`` — the *only* sanctioned randomness is
  a seeded generator constructed once and passed around:
  ``random.Random(seed)`` (and ``SystemRandom``/``SeedSequence`` for
  completeness) stay legal, ``random.random()``/``random.randrange()``
  etc. do not;
* ``np.random.<convenience>`` — ``np.random.default_rng`` /
  ``Generator`` / ``PCG64`` / ``SeedSequence`` are the seeded
  constructors and stay legal.

An **allow marker** — a ``# wall-clock: <why>`` comment with a
non-empty justification on the offending line — suppresses the finding
in place; it is how ``examples/fleet_sim.py`` pins its wall-vs-virtual
split (wall seconds are measured for the sweep report but must never
enter a rollup). Markers without a justification do not suppress.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding

ALLOW_MARKER = "# wall-clock:"

FORBIDDEN_TIME = frozenset({
    "time", "time_ns", "monotonic", "monotonic_ns", "sleep",
    "perf_counter", "perf_counter_ns", "process_time",
    "process_time_ns"})
FORBIDDEN_DATETIME = frozenset({"now", "utcnow", "today"})
ALLOWED_RANDOM = frozenset({"Random", "SystemRandom", "SeedSequence"})
ALLOWED_NP_RANDOM = frozenset({"default_rng", "Generator", "PCG64",
                               "BitGenerator", "SeedSequence"})


def _root_name(expr: ast.expr) -> Optional[str]:
    """Leftmost ``Name`` of an attribute chain (``a.b.c`` -> ``a``)."""
    while isinstance(expr, ast.Attribute):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _line_allowed(lines: Sequence[str], lineno: int) -> bool:
    """True when the source line carries a justified allow marker."""
    if not lines or lineno > len(lines):
        return False
    line = lines[lineno - 1]
    idx = line.find(ALLOW_MARKER)
    return idx >= 0 and bool(line[idx + len(ALLOW_MARKER):].strip())


class _Scope:
    """Tracks the dotted lexical symbol (Class.method) during the walk."""

    def __init__(self) -> None:
        self.parts: List[str] = []

    def symbol(self) -> str:
        return ".".join(self.parts) if self.parts else "<module>"


def _check_node(node: ast.AST, sym: str, path: str,
                lines: Sequence[str], findings: List[Finding]) -> None:
    def emit(message: str) -> None:
        if not _line_allowed(lines, node.lineno):
            findings.append(Finding("purity", path, node.lineno, sym,
                                    message))

    if isinstance(node, ast.Attribute):
        root = _root_name(node)
        base = node.value
        if isinstance(base, ast.Name) and base.id == "time" and \
                node.attr in FORBIDDEN_TIME:
            emit(f"wall-clock call time.{node.attr} in the virtual-clock "
                 f"domain")
        elif node.attr in FORBIDDEN_DATETIME and root is not None and \
                "date" in root.lower():
            emit(f"wall-clock call {root}.{node.attr} in the "
                 f"virtual-clock domain")
        elif isinstance(base, ast.Name) and base.id == "random" and \
                node.attr not in ALLOWED_RANDOM:
            emit(f"module-level random.{node.attr}: pass a seeded "
                 f"random.Random instead")
        elif isinstance(base, ast.Attribute) and base.attr == "random" \
                and isinstance(base.value, ast.Name) and \
                base.value.id in ("np", "numpy") and \
                node.attr not in ALLOWED_NP_RANDOM:
            emit(f"np.random.{node.attr} draws from the global numpy "
                 f"RNG: pass a seeded np.random.Generator instead")
    elif isinstance(node, ast.ImportFrom):
        if node.module == "time":
            for alias in node.names:
                if alias.name in FORBIDDEN_TIME:
                    emit(f"`from time import {alias.name}` in the "
                         f"virtual-clock domain")
        elif node.module == "random":
            for alias in node.names:
                if alias.name not in ALLOWED_RANDOM:
                    emit(f"`from random import {alias.name}`: pass a "
                         f"seeded random.Random instead")


def check_purity(tree: ast.Module, path: str, lines: Sequence[str],
                 class_filter: Optional[Iterable[str]] = None
                 ) -> List[Finding]:
    """Scan one module. With ``class_filter`` set, only the named
    top-level classes are in the purity domain (for mixed files like
    ``channel.py`` where only ``SimChannel`` is virtual-clock code);
    module-level imports are then out of scope too."""
    findings: List[Finding] = []
    wanted = None if class_filter is None else frozenset(class_filter)
    scope = _Scope()

    def visit(node: ast.AST, in_scope: bool) -> None:
        entered = False
        if isinstance(node, (ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            scope.parts.append(node.name)
            entered = True
            if wanted is not None and isinstance(node, ast.ClassDef) \
                    and node.name in wanted:
                in_scope = True
        if in_scope:
            _check_node(node, scope.symbol(), path, lines, findings)
        for child in ast.iter_child_nodes(node):
            visit(child, in_scope)
        if entered:
            scope.parts.pop()

    for stmt in tree.body:
        visit(stmt, wanted is None)
    return findings


def marker_lines(lines: Sequence[str]) -> List[Tuple[int, str]]:
    """All justified allow markers in a file, as ``(lineno, why)`` —
    lets tests pin exactly which lines opt out of the purity rule."""
    out: List[Tuple[int, str]] = []
    for i, line in enumerate(lines, 1):
        idx = line.find(ALLOW_MARKER)
        if idx >= 0:
            why = line[idx + len(ALLOW_MARKER):].strip()
            if why:
                out.append((i, why))
    return out
