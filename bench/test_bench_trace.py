"""The reduction from a profiler trace to device busy time, kernel events and
labelled idle gaps: by hand on made-up events, and on a small trace
recorded on one TPU v5e (``testdata/``: 0.2 s of the ``alexnet224.solo``
cell, one edge sending one request at a time)."""
from __future__ import annotations

import os

import pytest

from bench import model, peaks, trace
from bench.metrics import device_idle, masked_matmul_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "trace_alexnet224_solo.xplane.pb")
MS = 1_000_000          # nanoseconds


def test_busy_time_is_the_union_of_ops_inside_the_window():
    ops = {"/device:TPU:0": [
        ("m/%a", 0 * MS, 4 * MS),            # starts before the window
        ("m/%b", 12 * MS, 5 * MS),
        ("m/%c", 14 * MS, 4 * MS),           # overlaps %b
        ("m/%a", 30 * MS, 2 * MS),
        ("m/%d", 50 * MS, 5 * MS),           # after the window
    ]}
    spans = [("bench.trace_window", 2 * MS, 38 * MS),
             ("bench.request", 5 * MS, 20 * MS),
             ("bench.request", 19 * MS, 20 * MS),
             ("bench.wait_due", 33 * MS, 4 * MS)]
    s = trace.reduce_events(ops, spans)
    assert s.window_s == pytest.approx(0.038)
    assert s.busy_s == pytest.approx((2 + 6 + 2) * 1e-3)
    assert s.op_seconds["m/%a"] == pytest.approx(4e-3)
    assert s.op_events["m/%a"] == pytest.approx([4e-3, 2e-3])
    assert "m/%d" not in s.op_seconds
    # gaps: 4-12, 18-30, 32-40 ms; labelled by the spans held at their middle
    assert s.gaps == [("bench.request x2", pytest.approx(0.012)),
                      ("bench.request x1", pytest.approx(0.008)),
                      ("bench.request x1+bench.wait_due x1",
                       pytest.approx(0.008))]
    bd = trace.breakdown(s, n=2)
    assert [k for k, _ in bd["device_ops"]] == ["m/%b", "m/%a"]
    assert len(bd["idle_gaps"]) == 2


def test_a_trace_without_its_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": []}, [])


def test_op_name_is_the_hlo_instruction():
    assert trace.op_name("%fusion.9 = f32[1,2]{1,0} fusion(%p), kind=kLoop"
                         ) == "%fusion.9"


@pytest.fixture(scope="module")
def recorded():
    return trace.read(RECORDED)


def test_recorded_trace_reduces_to_one_busy_chip(recorded):
    assert recorded.n_devices == 1
    assert 0.1 < recorded.window_s < 0.5
    assert 0 < recorded.busy_s < recorded.window_s
    rec = {"trace": recorded}
    assert 0 < device_idle.read(rec) < 100


def test_recorded_trace_holds_whole_edge_forwards_of_the_kernel(recorded):
    cfg = model.load_config("alexnet224")
    rec = {"trace": recorded, "cfg": cfg,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    kernels = {k: v for k, v in recorded.op_events.items()
               if masked_matmul_roofline.KERNEL_NAME in k}
    assert len(kernels) == 5                    # one per conv of the edge
    n = {len(v) for v in kernels.values()}
    assert len(n) == 1 and n.pop() >= 1
    share = masked_matmul_roofline.read(rec)
    assert 0 < share <= 100
