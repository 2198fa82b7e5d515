"""Device time per execution of the program's named halves
(``device_time.py``, ``edge_device_ms``, ``cloud_device_ms``): by hand on
made-up events; on a trace recorded on one TPU v5e (``testdata/``: 0.1 s of
the ``alexnet224.sat`` cell) against the trace's raw module events; and
absent on a trace of programs without those names (the ``solo`` recording,
made before the halves had names) or through the harness."""
from __future__ import annotations

import os
import time

import pytest

from bench import device_time, run, trace
from bench.metrics import cloud_device_ms, edge_device_ms
from bench.test_bench_correct import LIMIT, PEAKS, TINY, TRAFFIC

HERE = os.path.dirname(os.path.abspath(__file__))
SAT = os.path.join(HERE, "testdata", "trace_alexnet224_sat.xplane.pb")
SOLO = os.path.join(HERE, "testdata", "trace_alexnet224_solo.xplane.pb")
MS = 1_000_000          # nanoseconds
WINDOW = ("bench.trace_window", 0, 100 * MS)


def _cloud_rows_call(t):
    """One bucket-2 cloud call at ``t`` ms: a copy, a ``%while`` whose
    two iterations the trace lists inside it, and the output: 4.6 ms."""
    m = "jit_cloud_c13_rows(111)/"
    return [(m + "%copy-start", t * MS, MS // 10),
            (m + "%while", (t + 0.1) * MS, 4 * MS),
            (m + "%fc6", (t + 0.5) * MS, 1.5 * MS),
            (m + "%fc6", (t + 2.2) * MS, 1.5 * MS),
            (m + "%out", (t + 4.1) * MS, MS // 2)]


def _edge_forward(t):
    """One edge forward at ``t`` ms: 1.5 ms of two operations."""
    m = "jit_edge_c13(333)/"
    return [(m + "%conv", t * MS, MS), (m + "%pool", (t + 1) * MS, MS // 2)]


def _summary(*ops):
    return trace.reduce_events({"/device:TPU:0": [o for g in ops for o in g]},
                               [WINDOW])


def test_a_loop_body_inside_its_while_is_counted_once():
    """Three calls, one of them without a 1 ns marker operation (as a
    recorded trace shows ``%custom-call`` in some calls only): the marker
    neither sets the executions nor drops the rest."""
    marker = [("jit_cloud_c13_rows(111)/%custom-call", t * MS + MS // 20, 1)
              for t in (10, 20)]
    s = _summary(marker, *(_cloud_rows_call(t) for t in (10, 20, 30)))
    assert device_time.per_execution_ms(s, "cloud") == pytest.approx(4.6)
    assert cloud_device_ms.read({"trace": s}) == pytest.approx(4.6)
    assert edge_device_ms.read({"trace": s}) is None


def test_each_compiled_shape_counts_its_own_executions():
    """Two fingerprints of one half are two modules: a bucket-1 call
    (2.5 ms, no loop) beside three bucket-2 calls (4.6 ms each)."""
    one = [("jit_cloud_c13_rows(222)/%fc", 50 * MS, 2 * MS),
           ("jit_cloud_c13_rows(222)/%out", 52 * MS, MS // 2)]
    s = _summary(*(_cloud_rows_call(t) for t in (10, 20, 30)), one)
    assert set(device_time.half_modules(s, "cloud")) == {
        "jit_cloud_c13_rows(111)", "jit_cloud_c13_rows(222)"}
    assert cloud_device_ms.read({"trace": s}) == pytest.approx(
        (3 * 4.6 + 2.5) / 4)


def test_executions_are_counted_by_the_longest_operation():
    """Four whole edge forwards and one cut by the window's start: five
    executions, the cut one counted only for its part in the window."""
    cut = [("jit_edge_c13(333)/%conv", -MS // 2, MS),
           ("jit_edge_c13(333)/%pool", MS // 2, MS // 2)]
    s = _summary(cut, *(_edge_forward(t) for t in (10, 20, 30, 40)))
    assert edge_device_ms.read({"trace": s}) == pytest.approx(
        (4 * 1.5 + 1.0) / 5)


@pytest.mark.parametrize("reader", [edge_device_ms, cloud_device_ms])
def test_readers_are_absent_where_the_halves_have_no_names(reader):
    """A program that jits both halves as ``traced`` gives modules named
    ``jit_traced(<fingerprint>)``: nothing to read, and no error."""
    parent = _summary([("jit_traced(4827)/%fusion.5", 10 * MS, MS),
                       ("jit_traced(7153)/%while", 20 * MS, 2 * MS)])
    assert reader.read({"trace": parent}) is None
    assert reader.read({"trace": None}) is None
    assert reader.read({"trace": trace.TraceSummary(
        window_s=1.0, busy_s=0.25, n_devices=1)}) is None


def test_traced_run_leaves_out_what_its_trace_does_not_hold(monkeypatch):
    """Through the harness: a traced run whose trace holds no named half
    (stubbed, as the CPU has no device plane) reports the metrics it can
    read and leaves the two device times out, without failing."""
    monkeypatch.setattr(trace, "read", lambda path: trace.TraceSummary(
        window_s=1.0, busy_s=0.25, n_devices=1))
    res = run.run_cell(TINY, TRAFFIC["sat"],
                       [("device_idle.sat", "%"), ("edge_device_ms.sat", "ms"),
                        ("cloud_device_ms.sat", "ms")],
                       seed=2**33 + 23, seconds=2.0, trace=True, limit=LIMIT,
                       t_start=time.perf_counter(), peaks=PEAKS)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"device_idle.sat"}


def _exact_ms(path, half):
    """Device ms per execution of ``half``, straight from the raw trace:
    each ``XLA Modules`` event of the half that starts in the window is one
    execution, and the union of the operations inside those events, cut to
    the window, is their device time."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = [(e.start_ns, e.start_ns + e.duration_ns) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name == trace.WINDOW_SPAN]
    (w0, w1), = host
    dev = next(p for p in pd.planes if p.name.startswith(trace.DEVICE_PREFIX))
    lines = {line.name: list(line.events) for line in dev.lines}
    mods = [(m.start_ns, m.start_ns + m.duration_ns)
            for m in lines[trace.MODULES_LINE]
            if m.name.startswith(f"jit_{half}_")]
    runs = sum(1 for s, _ in mods if w0 <= s < w1)
    ivs = [(max(o.start_ns, w0), min(o.start_ns + o.duration_ns, w1))
           for o in lines[trace.OPS_LINE]
           if any(s <= o.start_ns < e for s, e in mods)]
    busy = sum(b - a for a, b in trace._union([iv for iv in ivs
                                               if iv[1] > iv[0]]))
    return busy * 1e-6 / runs


@pytest.fixture(scope="module")
def recorded_sat():
    return trace.read(SAT)


@pytest.mark.parametrize("reader,half,lo,hi", [
    (edge_device_ms, "edge", 0.05, 5.0),
    (cloud_device_ms, "cloud", 0.2, 20.0)])
def test_recorded_sat_trace_reads_each_half_as_the_raw_events_do(
        recorded_sat, reader, half, lo, hi):
    ms = reader.read({"trace": recorded_sat})
    assert lo < ms < hi
    assert ms == pytest.approx(_exact_ms(SAT, half), rel=0.05)


@pytest.mark.parametrize("reader", [edge_device_ms, cloud_device_ms])
def test_recorded_trace_of_unnamed_halves_reads_absent(reader):
    assert reader.read({"trace": trace.read(SOLO)}) is None
