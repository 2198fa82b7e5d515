"""The analyzers analyzed: fixture snippets for every violation class,
plus the repo-wide gate.

Each checker is exercised twice per rule: a known-bad fixture (string
source compiled via ``ast.parse``) asserted to be *caught*, and a clean
twin asserted to be *silent* — the five violation classes the ISSUE
names (lock violation, wall-clock call, missing unit suffix,
digest-fold mismatch, pack/unpack drift) each appear as an explicit
fixture. The ``analysis``-marked tests at the bottom run the real gate
over the repo: ``src/`` must be green against the checked-in baseline,
``core/fleet/`` must be green *without* any baseline, and
``examples/fleet_sim.py``'s wall-vs-virtual timing split must stay
pinned to its two justified allow-marker lines.
"""
from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (BaselineEntry, apply_baseline, run_analysis)
from repro.analysis.baseline import load_baseline
from repro.analysis.concurrency import check_concurrency
from repro.analysis.contracts import (check_digest_fold, check_pack_unpack,
                                      check_unit_suffixes)
from repro.analysis.purity import check_purity, marker_lines
from repro.analysis.registry import ClosureVar, SharedAttr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "analysis_baseline.json")


def _parse(src: str):
    src = textwrap.dedent(src)
    return ast.parse(src), src.splitlines()


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# concurrency: lock discipline
# ---------------------------------------------------------------------------
THREADED_BAD = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            threading.Thread(target=self._loop).start()

        def _loop(self):
            self.count += 1          # write without the lock
"""

THREADED_GOOD = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            threading.Thread(target=self._loop).start()

        def _loop(self):
            with self._lock:
                self.count += 1
"""


def test_lock_violation_caught():
    tree, _ = _parse(THREADED_BAD)
    reg = (SharedAttr("Engine", "count", lock="_lock"),)
    findings = check_concurrency(tree, "x.py", reg)
    assert _rules(findings) == ["lock-discipline"]
    assert "Engine.count" in findings[0].symbol


def test_lock_guarded_clean():
    tree, _ = _parse(THREADED_GOOD)
    reg = (SharedAttr("Engine", "count", lock="_lock"),)
    assert check_concurrency(tree, "x.py", reg) == []


def test_unregistered_thread_write_caught():
    tree, _ = _parse(THREADED_BAD)
    findings = check_concurrency(tree, "x.py", ())
    assert _rules(findings) == ["unguarded-shared-write"]


def test_init_writes_exempt():
    # __init__ publishes before any thread exists: never flagged
    tree, _ = _parse(THREADED_GOOD)
    findings = check_concurrency(
        tree, "x.py", (SharedAttr("Engine", "_lock", lock="_lock"),))
    assert findings == []


def test_subscript_store_caught():
    tree, _ = _parse("""
        import threading

        class Bank:
            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self.cache[0] = 1
    """)
    reg = (SharedAttr("Bank", "cache", lock="_lock"),)
    findings = check_concurrency(tree, "x.py", reg)
    assert "lock-discipline" in _rules(findings)
    # the lock itself is also stale (never assigned) — drift detection
    assert "stale-registry" in _rules(findings)


def test_thread_reachability_transitive():
    # a write two self-calls away from the thread entry is still flagged
    tree, _ = _parse("""
        import threading

        class Deep:
            def start(self):
                threading.Thread(target=self._entry).start()

            def _entry(self):
                self._step()

            def _step(self):
                self.state = 1
    """)
    findings = check_concurrency(tree, "x.py", ())
    assert _rules(findings) == ["unguarded-shared-write"]
    assert findings[0].symbol == "Deep.state"


def test_closure_var_lock_rule():
    bad = """
        import threading

        def serve(stats=None):
            lock = threading.Lock()

            def _worker():
                stats["n"] = stats.get("n", 0) + 1

            threading.Thread(target=_worker).start()
    """
    tree, _ = _parse(bad)
    reg = (ClosureVar("serve", "stats", lock="lock"),)
    findings = check_concurrency(tree, "x.py", reg)
    assert _rules(findings) == ["lock-discipline"]
    good = bad.replace('stats["n"] = stats.get("n", 0) + 1',
                       'with lock:\n'
                       '                    stats["n"] = 1')
    tree, _ = _parse(good)
    assert check_concurrency(tree, "x.py", reg) == []


def test_stale_registry_class_and_attr():
    tree, _ = _parse(THREADED_GOOD)
    findings = check_concurrency(tree, "x.py", (
        SharedAttr("Gone", "count", lock="_lock"),
        SharedAttr("Engine", "vanished", lock="_lock")))
    assert _rules(findings).count("stale-registry") == 2


def test_ownership_requires_justification():
    tree, _ = _parse(THREADED_GOOD)
    findings = check_concurrency(
        tree, "x.py", (SharedAttr("Engine", "count", lock=None, note=""),))
    assert "registry-justification" in _rules(findings)
    findings = check_concurrency(
        tree, "x.py",
        (SharedAttr("Engine", "count", lock=None, note="single owner"),))
    assert findings == []


# ---------------------------------------------------------------------------
# purity: wall clock and ambient randomness
# ---------------------------------------------------------------------------
def test_wallclock_call_caught():
    tree, lines = _parse("""
        import time

        def tick(q):
            return time.time() - q
    """)
    findings = check_purity(tree, "x.py", lines)
    assert _rules(findings) == ["purity"]
    assert "time.time" in findings[0].message


def test_sleep_and_monotonic_caught():
    tree, lines = _parse("""
        import time

        def nap():
            time.sleep(0.1)
            return time.monotonic()
    """)
    assert len(check_purity(tree, "x.py", lines)) == 2


def test_module_random_caught_seeded_rng_clean():
    tree, lines = _parse("""
        import random

        def draw():
            return random.random()
    """)
    assert _rules(check_purity(tree, "x.py", lines)) == ["purity"]
    tree, lines = _parse("""
        import random

        def draw(seed):
            rng = random.Random(seed)
            return rng.random()
    """)
    assert check_purity(tree, "x.py", lines) == []


def test_np_random_convenience_caught_generator_clean():
    tree, lines = _parse("""
        import numpy as np

        def draw():
            return np.random.rand(3)
    """)
    assert _rules(check_purity(tree, "x.py", lines)) == ["purity"]
    tree, lines = _parse("""
        import numpy as np

        def draw(seed):
            return np.random.default_rng(seed).random(3)
    """)
    assert check_purity(tree, "x.py", lines) == []


def test_purity_class_scope_filter():
    src = """
        import time

        def outside():
            return time.time()      # not in the scanned class: ignored

        class Sim:
            def step(self):
                return time.monotonic()
    """
    tree, lines = _parse(src)
    findings = check_purity(tree, "x.py", lines, class_filter=("Sim",))
    assert len(findings) == 1 and findings[0].symbol == "Sim.step"


def test_allow_marker_needs_justification():
    justified = """
        import time

        def bench():
            return time.perf_counter()  # wall-clock: sweep timing only
    """
    tree, lines = _parse(justified)
    assert check_purity(tree, "x.py", lines) == []
    bare = justified.replace("# wall-clock: sweep timing only",
                             "# wall-clock:")
    tree, lines = _parse(bare)
    assert _rules(check_purity(tree, "x.py", lines)) == ["purity"]


# ---------------------------------------------------------------------------
# contracts: unit suffixes, digest fold, pack/unpack
# ---------------------------------------------------------------------------
def test_missing_unit_suffix_caught():
    tree, _ = _parse("""
        class Policy:
            def to_json(self):
                return {"upload_wait": self.w, "max_batch": 4}
    """)
    findings = check_unit_suffixes(tree, "x.py", ["Policy"])
    assert _rules(findings) == ["unit-suffix"]
    assert "upload_wait" in findings[0].symbol


def test_unit_and_dimensionless_suffixes_clean():
    tree, _ = _parse("""
        class Policy:
            def to_json(self):
                return {"max_wait_ms": 1, "battery_j": 2,
                        "backoff_jitter": 0.1, "latency_weight": 1.0,
                        "base_rate_hz": 5.0, "seed": 7}
    """)
    assert check_unit_suffixes(tree, "x.py", ["Policy"]) == []


def test_unit_suffix_registry_drift():
    tree, _ = _parse("class Other:\n    pass\n")
    findings = check_unit_suffixes(tree, "x.py", ["Policy", "Other"])
    assert _rules(findings).count("stale-registry") == 2   # missing class
    # ... and a present class without to_json


DIGEST_BAD = """
    class Plan:
        def contract(self):
            doc = {"split": self.split}
            doc["energy"] = self.energy.to_json()   # unguarded fold
            if self.batching is not None:
                doc["batching"] = self.batching.to_json()
            return doc
"""


def test_digest_fold_mismatch_caught():
    tree, _ = _parse(DIGEST_BAD)
    findings = check_digest_fold(tree, "x.py", "Plan", "contract",
                                 ["energy", "batching"])
    assert _rules(findings) == ["digest-fold"]
    assert "energy" in findings[0].symbol


def test_digest_fold_guarded_clean_and_missing_section():
    tree, _ = _parse(DIGEST_BAD)
    findings = check_digest_fold(tree, "x.py", "Plan", "contract",
                                 ["batching", "faults"])
    assert _rules(findings) == ["digest-fold"]       # faults never folded
    assert "faults" in findings[0].symbol


def test_pack_unpack_drift_caught():
    tree, _ = _parse("""
        import struct

        def enc(a, b):
            return struct.pack("<II", a, b)

        def dec(buf):
            return struct.unpack("<I", buf)      # drifted: one field
    """)
    findings = check_pack_unpack(tree, "x.py")
    assert _rules(findings) == ["pack-unpack"]
    assert "<II" in findings[0].symbol


def test_pack_unpack_fstring_normalized_clean():
    tree, _ = _parse("""
        import struct

        def enc(arr):
            return struct.pack(f"<{arr.ndim}Q", *arr.shape)

        def dec(buf, ndim):
            return struct.unpack_from(f"<{ndim}Q", buf, 0)
    """)
    assert check_pack_unpack(tree, "x.py") == []


def test_struct_var_pack_without_unpack_caught():
    tree, _ = _parse("""
        from struct import Struct
        HDR = Struct("<IH")

        def enc(v):
            return HDR.pack(1, v)
    """)
    findings = check_pack_unpack(tree, "x.py")
    assert _rules(findings) == ["pack-unpack"]
    assert findings[0].symbol == "HDR"


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------
def _one_finding():
    tree, lines = _parse("import time\nt = time.time()\n")
    return check_purity(tree, "x.py", lines)


def test_baseline_suppresses_with_justification():
    findings = _one_finding()
    entry = BaselineEntry("purity", "x.py", findings[0].symbol,
                          justification="known demo-mode clock read")
    unsuppressed, suppressed = apply_baseline(findings, [entry])
    assert unsuppressed == [] and len(suppressed) == 1


def test_baseline_without_justification_is_a_finding():
    findings = _one_finding()
    entry = BaselineEntry("purity", "x.py", findings[0].symbol)
    unsuppressed, _ = apply_baseline(findings, [entry])
    rules = _rules(unsuppressed)
    assert "purity" in rules and "baseline-justification" in rules


def test_stale_suppression_is_a_finding():
    entry = BaselineEntry("purity", "gone.py", "Gone.symbol",
                          justification="was fixed long ago")
    unsuppressed, _ = apply_baseline([], [entry])
    assert _rules(unsuppressed) == ["stale-suppression"]


def test_partial_scan_cannot_declare_staleness():
    """A run that never analyzed an entry's file must not call the
    entry stale — only a scan covering that path decides."""
    entry = BaselineEntry("purity", "src/a.py", "A.m",
                          justification="single-owner demo path")
    unsuppressed, _ = apply_baseline([], [entry],
                                     scanned_paths={"src/other.py"})
    assert unsuppressed == []
    unsuppressed, _ = apply_baseline([], [entry],
                                     scanned_paths={"src/a.py"})
    assert _rules(unsuppressed) == ["stale-suppression"]


@pytest.mark.analysis
def test_fleet_sim_partial_run_with_real_baseline():
    """The CI step analyzes examples/fleet_sim.py alone against the
    checked-in baseline: the SimChannel entry's file is out of scope
    for that run, so it must not surface as a stale suppression."""
    report = run_analysis([os.path.join(REPO, "examples",
                                        "fleet_sim.py")],
                          baseline_path=BASELINE)
    assert report.ok, report.render()


# ---------------------------------------------------------------------------
# the repo-wide gate
# ---------------------------------------------------------------------------
@pytest.mark.analysis
def test_repo_gate_green_with_baseline():
    """`python -m repro.analysis` semantics: src/ has zero unsuppressed
    findings against the checked-in baseline."""
    report = run_analysis([os.path.join(REPO, "src")],
                          baseline_path=BASELINE)
    assert report.ok, "unsuppressed findings:\n" + report.render()
    assert report.n_files > 50


@pytest.mark.analysis
def test_baseline_entries_all_justified():
    for entry in load_baseline(BASELINE):
        assert entry.justification.strip(), f"unjustified: {entry}"


@pytest.mark.analysis
def test_fleet_tree_pure_without_baseline():
    """core/fleet/ determinism is checker-clean with NO suppressions —
    the bit-identity contract rides on this."""
    report = run_analysis(
        [os.path.join(REPO, "src", "repro", "core", "fleet")], entries=[])
    purity = [f for f in report.findings if f.rule == "purity"]
    assert purity == [], "\n".join(f.render() for f in purity)


@pytest.mark.analysis
def test_fleet_sim_wall_clock_pinned():
    """examples/fleet_sim.py: exactly its two sweep-timing lines carry
    justified wall-clock markers; everything else is virtual-clock
    pure. Moving a wall read elsewhere breaks this test."""
    path = os.path.join(REPO, "examples", "fleet_sim.py")
    report = run_analysis([path], entries=[])
    assert report.ok, report.render()
    with open(path) as f:
        lines = f.read().splitlines()
    markers = marker_lines(lines)
    assert len(markers) == 2, markers
    for lineno, _ in markers:
        assert "perf_counter" in lines[lineno - 1]


@pytest.mark.analysis
def test_cli_json_report():
    """The CLI exits 0 on src/ and emits a well-formed JSON report."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--json"],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert doc["findings"] == []
    assert {f["rule"] for f in doc["suppressed"]} <= {"purity"}
