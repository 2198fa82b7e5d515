"""The served socket path's host spans, recorded by a real profiler trace
on the CPU: two pipelined edges against one batching cloud, every
``repro.*`` stage of every request present (once per data frame, which
carries a group of requests), and the batch spans agreeing with the
batcher's own counters."""
from __future__ import annotations

import glob
import threading
from collections import Counter

import jax
import numpy as np
import pytest

from repro import serving
from repro.core.collab.batching import BatchingPolicy
from repro.models.cnn import init_cnn_params, tiny_cnn_config

SPLIT = 3
#: one span per data frame of these stages
PER_FRAME = ("repro.edge.forward", "repro.edge.encode",
             "repro.cloud.decode", "repro.cloud.encode",
             "repro.edge.decode")
#: the frame's stages on the edge that name its rows
WITH_ROWS = ("repro.edge.forward", "repro.edge.encode")
#: one span per cloud call
PER_BATCH = ("repro.batcher.fill", "repro.cloud.call")


def _host_spans(path):
    """(name, {arg: value}) of every ``repro.*`` event on the host planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


@pytest.fixture(scope="module")
def traced_window(tmp_path_factory):
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(1)
    imgs = [rng.rand(1, 32, 32, 3).astype(np.float32) for _ in range(6)]
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, compact=False, codec="int8", shape_link=False,
        port=29865, batching=BatchingPolicy(max_batch=4, max_wait_ms=5.0))
    out = tmp_path_factory.mktemp("trace")
    with serving.CloudServer(plan, max_clients=None) as srv:
        sessions = [serving.connect(plan, backend="socket") for _ in range(2)]
        try:
            jax.profiler.start_trace(str(out))
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    ts = [threading.Thread(target=s.infer_many, args=(imgs,))
                          for s in sessions]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join(60)
            finally:
                jax.profiler.stop_trace()
            assert not any(t.is_alive() for t in ts)
            lane = next(iter(srv.lane_stats().values()))
        finally:
            for s in sessions:
                s.close()
    paths = glob.glob(str(out / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(paths) == 1
    return _host_spans(paths[0]), lane, 2 * len(imgs)


def test_every_request_stage_has_one_span_per_request(traced_window):
    """Each edge's 6 requests travel as two frames (4 + 2 rows at
    ``max_batch`` 4): every stage has one span a frame, and the rows the
    edge spans name cover each request once."""
    spans, lane, requests = traced_window
    n = Counter(name for name, _ in spans)
    assert set(n) == set(PER_FRAME) | set(PER_BATCH)
    assert lane["frames"] == 4
    for name in PER_FRAME:
        assert n[name] == lane["frames"], name
    for name in WITH_ROWS:
        assert sorted(a["rows"] for nm, a in spans if nm == name) == [
            2, 2, 4, 4], name
        assert sum(a["rows"] for nm, a in spans if nm == name) == requests


def test_batch_spans_match_the_batcher_counters(traced_window):
    spans, lane, requests = traced_window
    n = Counter(name for name, _ in spans)
    assert n["repro.batcher.fill"] == n["repro.cloud.call"] == lane["batches"]
    calls = [args for name, args in spans if name == "repro.cloud.call"]
    assert {a["split"] for a in calls} == {SPLIT}
    assert sum(a["rows"] for a in calls) == lane["rows"] == requests
    assert all(a["bucket"] >= a["rows"] for a in calls)
    assert sum(args["rows"] for name, args in spans
               if name == "repro.batcher.fill") == requests
