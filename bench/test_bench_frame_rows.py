"""``frame_rows``: rows per frame from a run record's lane counters, absent
where a record has no lane or no frames; and on a tiny served window on the
CPU, where each edge's chunk of 4 requests travels as one frame."""
from __future__ import annotations

import time

import pytest

from bench import run
from bench.metrics import frame_rows
from bench.test_bench_correct import LIMIT, PEAKS, TINY, TRAFFIC


@pytest.mark.parametrize("lane,want", [
    ({"rows": 64, "frames": 16, "batches": 8}, 4.0),
    ({"rows": 10, "frames": 10, "batches": 2}, 1.0),
    ({"rows": 7, "frames": 2, "batches": 1}, 3.5),
])
def test_reads_rows_over_frames(lane, want):
    assert frame_rows.read({"lane": lane}) == want


@pytest.mark.parametrize("rec", [{}, {"lane": None}, {"lane": {}},
                                 {"lane": {"rows": 0, "frames": 0}}])
def test_absent_without_lane_or_frames(rec):
    assert frame_rows.read(rec) is None


def test_tiny_sat_window_sends_a_chunk_per_frame():
    """The ``sat`` loop's chunk of 4 fits ``max_batch`` 8, so every frame
    that reaches the batcher carries 4 rows."""
    traffic = TRAFFIC["sat"]
    res = run.run_cell(TINY, traffic,
                       [("frame_rows.sat", "rows"), ("req_per_s", "req/s"),
                        ("setup_s", "s")],
                       seed=2**32 + 5, seconds=1.0, trace=False,
                       limit=LIMIT, t_start=time.perf_counter(), peaks=PEAKS)
    assert res["correct"], res["checks"]
    assert res["metrics"]["frame_rows.sat"]["value"] == traffic["chunk"]
