"""Cross-client dynamic batching engine: bit-identical batched logits
across codecs, lane isolation, window flush, ragged-batch padding,
bucketed-compilation warm (no steady-state tracing), the shared
server-side link shaper, and the plan's ``batching`` contract section."""
from __future__ import annotations

import socket
import threading
import time

import jax
import numpy as np
import pytest

from repro import serving
from repro.core.collab.batching import (BatchingPolicy, DynamicBatcher,
                                        bucket_for, default_buckets)
from repro.core.collab.channel import LinkShaper, ShapedSocket
from repro.core.collab.protocol import (decode_any, encode_feature,
                                        frame_lane)
from repro.core.collab.runtime import SplitFnBank
from repro.core.partition.profiles import LinkProfile
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.models.cnn import (init_cnn_params, prunable_layers,
                              tiny_cnn_config)

SPLIT = 3


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    masks = cnn_masks_from_ratios(
        params, cfg, {i: 0.5 for i in prunable_layers(cfg)})
    rng = np.random.RandomState(0)
    imgs = [rng.rand(1, 32, 32, 3).astype(np.float32) for _ in range(11)]
    return cfg, params, masks, imgs


@pytest.fixture(scope="module")
def bank(setup):
    cfg, params, masks, _ = setup
    return SplitFnBank(params, cfg, masks, compact=True)


# ---------------------------------------------------------------------------
# policy + buckets
# ---------------------------------------------------------------------------
def test_default_buckets_and_bucket_for():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(6) == (1, 2, 4, 6)
    assert default_buckets(1) == (1,)
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    assert bucket_for(8, (1, 2, 4, 8)) == 8
    with pytest.raises(ValueError):
        bucket_for(9, (1, 2, 4, 8))


def test_policy_validation_and_json_roundtrip():
    p = BatchingPolicy(max_batch=8, max_wait_ms=2.5, buckets=(1, 4, 8))
    assert BatchingPolicy.from_json(p.to_json()) == p
    assert p.resolved_buckets == (1, 4, 8)
    assert BatchingPolicy(max_batch=6).resolved_buckets == (1, 2, 4, 6)
    with pytest.raises(ValueError):
        BatchingPolicy(max_batch=0)
    with pytest.raises(ValueError):
        BatchingPolicy(max_batch=8, max_wait_ms=-1)
    with pytest.raises(ValueError):
        BatchingPolicy(max_batch=8, buckets=(1, 4))      # must end at max
    with pytest.raises(ValueError):
        BatchingPolicy(max_batch=8, buckets=(4, 1, 8))   # must be sorted


# ---------------------------------------------------------------------------
# the engine: bit-identity, lanes, flush, padding
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec,pack", [("fp32", False), ("fp16", False),
                                        ("int8", True)])
def test_batched_logits_bit_identical_across_codecs(setup, codec, pack):
    """Batched-vs-sequential must agree BITWISE per codec: frames are
    encoded/decoded exactly as the sequential path does it, then fused
    through the engine's row-mapped cloud call."""
    cfg, params, masks, imgs = setup
    bank = SplitFnBank(params, cfg, masks, compact=False, pack=pack)
    edge_fn, cloud_fn, keep = bank.get(SPLIT)
    frames = [encode_feature(np.asarray(edge_fn(im)), codec=codec,
                             keep=keep) for im in imgs]
    decoded = [decode_any(f)[0] for f in frames]
    sequential = [np.asarray(cloud_fn(d)) for d in decoded]

    eng = DynamicBatcher(bank, BatchingPolicy(max_batch=8, max_wait_ms=20.0))
    futs = [eng.submit(SPLIT, frame_lane(frames[i]), decoded[i])
            for i in range(len(imgs))]
    outs = [f.result(timeout=30) for f in futs]
    eng.stop()
    for seq, got in zip(sequential, outs):
        assert np.array_equal(seq, got)
    stats = next(iter(eng.stats().values()))
    assert stats["batches"] < len(imgs)          # genuinely fused


def test_mixed_split_lanes_are_isolated(setup, bank):
    """Tensors for different splits have different shapes — the engine
    must key them into separate lanes and answer each with the right
    cloud sub-model."""
    cfg, params, masks, imgs = setup
    splits = (2, 5)
    feats = {c: [np.asarray(bank.get(c)[0](im)) for im in imgs[:4]]
             for c in splits}
    want = {c: [np.asarray(bank.get(c)[1](f)) for f in feats[c]]
            for c in splits}
    eng = DynamicBatcher(bank, BatchingPolicy(max_batch=4, max_wait_ms=10.0))
    futs = [(c, i, eng.submit(c, "fp32", feats[c][i]))
            for i in range(4) for c in splits]          # interleaved
    for c, i, f in futs:
        assert np.array_equal(want[c][i], f.result(timeout=30))
    eng.stop()
    stats = eng.stats()
    assert len(stats) == 2                       # one lane per split
    for lane in stats.values():
        assert lane["rows"] == 4


def test_partial_batch_flushes_on_window(setup, bank):
    """3 requests < max_batch must not wait forever: the window expires
    and the partial batch runs (padded to the next bucket)."""
    eng = DynamicBatcher(bank, BatchingPolicy(max_batch=8, max_wait_ms=30.0))
    imgs = setup[3]
    feats = [np.asarray(bank.get(SPLIT)[0](im)) for im in imgs[:3]]
    t0 = time.perf_counter()
    futs = [eng.submit(SPLIT, "fp32", f) for f in feats]
    outs = [f.result(timeout=10) for f in futs]
    elapsed = time.perf_counter() - t0
    eng.stop()
    assert elapsed < 5.0                         # flushed, not starved
    want = [np.asarray(bank.get(SPLIT)[1](f)) for f in feats]
    for a, b in zip(want, outs):
        assert np.array_equal(a, b)
    lane = next(iter(eng.stats().values()))
    assert lane["batch_sizes"] == [3]
    assert lane["padded_rows"] == 1              # 3 padded to bucket 4
    assert lane["padding_waste"] == pytest.approx(0.25)


def test_ragged_final_batch_padding_masked_out(setup, bank):
    """Padded rows (zeros) must never leak into returned logits, and a
    multi-row frame comes back with exactly its own rows."""
    cfg, params, masks, imgs = setup
    edge_fn, cloud_fn, _ = bank.get(SPLIT)
    feats5 = np.concatenate([np.asarray(edge_fn(im)) for im in imgs[:5]],
                            axis=0)
    want = np.concatenate([np.asarray(cloud_fn(np.asarray(edge_fn(im))))
                           for im in imgs[:5]], axis=0)
    eng = DynamicBatcher(bank, BatchingPolicy(max_batch=8, max_wait_ms=5.0))
    out = eng.submit(SPLIT, "fp32", feats5).result(timeout=30)
    eng.stop()
    assert out.shape[0] == 5                     # bucket-8 padding removed
    assert np.array_equal(want, out)
    lane = next(iter(eng.stats().values()))
    assert lane["padded_rows"] == 3


def test_lone_frame_waits_out_the_window(setup, bank):
    """A frame that no other frame joins waits the whole batch window
    before its cloud call, and ``queue_wait_s`` counts that wait once."""
    eng = DynamicBatcher(bank, BatchingPolicy(max_batch=8, max_wait_ms=30.0))
    feat = np.asarray(bank.get(SPLIT)[0](setup[3][0]))
    eng.submit(SPLIT, "fp32", feat).result(timeout=30)
    eng.stop()
    lane = next(iter(eng.stats().values()))
    assert lane["frames"] == 1
    assert lane["queue_wait_s"] >= 0.030


def test_bank_names_each_program_by_half_and_split(setup, bank):
    """A profiler trace names a device program after its jitted function:
    each half carries its side and split, the row-mapped variant
    ``_rows``, so a trace tells the edge from the cloud by name."""
    x = setup[3][0]
    edge_fn, cloud_fn, _ = bank.get(SPLIT)
    feat = np.asarray(edge_fn(x))
    _, cloud_rows, _ = bank.get(SPLIT, batch_bucket=2)

    def module(fn, arg):
        return fn.func.lower(*fn.args, arg).as_text().split()[1]
    assert module(edge_fn, x) == f"@jit_edge_c{SPLIT}"
    assert module(cloud_fn, feat) == f"@jit_cloud_c{SPLIT}"
    assert (module(cloud_rows, np.repeat(feat, 2, axis=0))
            == f"@jit_cloud_c{SPLIT}_rows")


def test_submit_rejects_oversized_frame(setup, bank):
    eng = DynamicBatcher(bank, BatchingPolicy(max_batch=2))
    with pytest.raises(ValueError):
        eng.submit(SPLIT, "fp32", np.zeros((3, 16, 16, 48), np.float32))
    eng.stop()


# ---------------------------------------------------------------------------
# bucketed compilation: warm covers splits x buckets, steady state quiet
# ---------------------------------------------------------------------------
def test_warm_buckets_then_no_new_tracing(setup):
    """Satellite regression: ``warm`` used to pre-jit batch-1 only. After
    warming the configured buckets, batched calls at any fused size must
    perform no new tracing."""
    cfg, params, masks, imgs = setup
    bank = SplitFnBank(params, cfg, masks, compact=True)
    policy = BatchingPolicy(max_batch=8, max_wait_ms=5.0)
    splits = (2, SPLIT)
    bank.warm(splits, np.zeros((1, 32, 32, 3), np.float32),
              buckets=policy.resolved_buckets)
    baseline = bank.n_traces
    assert baseline > 0
    eng = DynamicBatcher(bank, policy)
    for c in splits:
        feats = [np.asarray(bank.get(c)[0](im)) for im in imgs]
        futs = [eng.submit(c, "fp32", f) for f in feats]   # 11 -> 8 + 3(4)
        for f in futs:
            f.result(timeout=30)
    eng.stop()
    assert bank.n_traces == baseline, (
        f"batched serving traced {bank.n_traces - baseline} new "
        f"function(s) after warm")


def test_unwarmed_bucket_does_trace(setup):
    """Sanity for the counter itself: a bucket shape warm never saw DOES
    trace (so the regression test above is meaningful)."""
    cfg, params, masks, imgs = setup
    bank = SplitFnBank(params, cfg, masks, compact=True)
    bank.warm([SPLIT], np.zeros((1, 32, 32, 3), np.float32), buckets=(1, 2))
    baseline = bank.n_traces
    feats = np.repeat(np.asarray(bank.get(SPLIT)[0](imgs[0])), 4, axis=0)
    jax.block_until_ready(bank.get(SPLIT, batch_bucket=4)[1](feats))
    assert bank.n_traces > baseline


# ---------------------------------------------------------------------------
# shared link shaper (one token bucket per physical medium)
# ---------------------------------------------------------------------------
def _timed_send(sock, nbytes, out, i):
    payload = b"x" * nbytes
    t0 = time.perf_counter()
    sock.sendall(payload)
    out[i] = time.perf_counter() - t0


def test_two_senders_on_shared_shaper_halve_goodput():
    """Satellite regression: two concurrent edges used to each get a
    private token bucket — 2x the physical link. On a shared shaper they
    contend: per-edge goodput halves (wall doubles)."""
    link = LinkProfile("test 4 MB/s", bandwidth=4e6, rtt_s=0.0)
    nbytes = 200_000                              # 50 ms alone at 4 MB/s

    def drain(s):
        try:
            while s.recv(1 << 16):
                pass
        except OSError:
            pass

    def run(n_senders, shared):
        pairs = [socket.socketpair() for _ in range(n_senders)]
        shaper = LinkShaper(link) if shared else None
        socks = [ShapedSocket(a, link, shaper=shaper) for a, _ in pairs]
        for _, b in pairs:
            threading.Thread(target=drain, args=(b,), daemon=True).start()
        out = [0.0] * n_senders
        ts = [threading.Thread(target=_timed_send,
                               args=(s, nbytes, out, i))
              for i, s in enumerate(socks)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for a, b in pairs:
            a.close()
            b.close()
        return max(out)

    alone = run(1, shared=True)
    together = run(2, shared=True)
    private = run(2, shared=False)
    # shared medium: 2 senders take ~2x the single-sender wall;
    # private buckets (the old bug) let both finish in ~1x
    assert together >= 1.6 * alone, (alone, together)
    assert private <= 1.4 * alone, (alone, private)


def test_serve_cloud_connections_share_one_shaper(setup, monkeypatch):
    """Structural check on the server: every connection handler's
    ShapedSocket must draw from the same LinkShaper instance."""
    import repro.core.collab.runtime as rt
    cfg, params, masks, imgs = setup
    seen = []
    real = rt.ShapedSocket

    class Recording(real):
        def __init__(self, sock, link, chunk=16384, trace=None,
                     shaper=None):
            seen.append(shaper)
            super().__init__(sock, link, chunk=chunk, trace=trace,
                             shaper=shaper)

    monkeypatch.setattr(rt, "ShapedSocket", Recording)
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, shape_link=True,
        port=29860)
    with serving.CloudServer(plan, max_clients=None) as srv:
        sessions = [serving.connect(plan, backend="socket")
                    for _ in range(2)]
        for s in sessions:
            s.infer(imgs[0])
        for s in sessions:
            s.close()
        srv.stop()
    server_side = [sh for sh in seen if sh is not None]
    assert len(server_side) >= 2
    assert len({id(sh) for sh in server_side}) == 1


# ---------------------------------------------------------------------------
# plan contract: the batching section
# ---------------------------------------------------------------------------
def test_plan_batching_digest_semantics(setup):
    cfg, params, masks, _ = setup

    def mk(**kw):
        return serving.DeploymentPlan.from_args(
            params, cfg, SPLIT, masks=masks, compact=True, **kw)

    plain = mk()
    batched = mk(batching=BatchingPolicy(max_batch=8))
    assert plain.digest != batched.digest        # folded when set
    assert mk().digest == plain.digest           # pre-batching stable
    assert batched.digest == mk(
        batching=BatchingPolicy(max_batch=8)).digest
    assert batched.digest != mk(
        batching=BatchingPolicy(max_batch=4)).digest
    assert "batched" in batched.describe()


def test_plan_batching_save_load_roundtrip(setup, tmp_path):
    cfg, params, masks, _ = setup
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True,
        batching=BatchingPolicy(max_batch=4, max_wait_ms=7.0))
    path = plan.save(str(tmp_path / "plan"))
    got = serving.DeploymentPlan.load(path)
    assert got.digest == plan.digest
    assert got.batching == plan.batching


# ---------------------------------------------------------------------------
# end to end: batched socket serving + local fast path
# ---------------------------------------------------------------------------
def test_batched_socket_serving_bit_identical_and_batching(setup):
    """2 pipelined edges against one batched cloud: logits bit-identical
    to sequential local serving, and the server's lane stats prove
    cross-client fusion actually happened."""
    cfg, params, masks, imgs = setup
    policy = BatchingPolicy(max_batch=8, max_wait_ms=10.0)
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        shape_link=False, port=29861, batching=policy)
    ref_plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        shape_link=False)
    with serving.connect(ref_plan, backend="local") as ref_sess:
        ref = [ref_sess.infer(im)["logits"] for im in imgs]

    outs = [None, None]
    with serving.CloudServer(plan, max_clients=None) as srv:
        def edge(i):
            with serving.connect(plan, backend="socket") as s:
                outs[i] = s.infer_many(imgs)
        ts = [threading.Thread(target=edge, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        srv.stop()
        stats = dict(srv.batch_stats)
    for per_edge in outs:
        for a, b in zip(ref, per_edge):
            assert np.array_equal(a, b["logits"])
    lane = next(iter(stats.values()))
    assert lane["rows"] == 2 * len(imgs)
    assert lane["avg_batch"] > 1.0               # cross-client fusion


def test_cloud_server_lane_stats_read_live(setup):
    """``CloudServer.lane_stats`` reads the batcher's counters while the
    server runs, the same records ``batch_stats`` gets at shutdown, and
    is empty for a plan that does not batch. ``infer_many`` sends its 5
    single-row requests at ``max_batch`` 4 as two frames (4 + 1 rows)."""
    cfg, params, masks, imgs = setup
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        shape_link=False, port=29863,
        batching=BatchingPolicy(max_batch=4, max_wait_ms=2.0))
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            s.infer_many(imgs[:5])
        live = srv.lane_stats()
        srv.stop()
    lane = next(iter(live.values()))
    assert lane["rows"] == 5 and lane["frames"] == 2
    assert lane["queue_wait_s"] > 0
    assert srv.batch_stats[next(iter(live))]["rows"] == 5
    plain = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        shape_link=False, port=29864)
    with serving.CloudServer(plain, max_clients=None) as srv:
        assert srv.lane_stats() == {}


# ---------------------------------------------------------------------------
# socket infer_many: one data frame per group of requests
# ---------------------------------------------------------------------------
def _int8_plan(setup, port, **kw):
    cfg, params, masks, _ = setup
    return serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        shape_link=False, port=port, **kw)


def _lane_delta(srv, before):
    after = next(iter(srv.lane_stats().values()))
    return {k: after[k] - before.get(k, 0) for k in ("rows", "frames")}


def test_socket_infer_many_groups_bit_identical_to_infer(setup):
    """On an int8, compacted, batching plan ``infer_many`` of n single-row
    requests sends ceil(n / max_batch) frames, answers bitwise as n
    ``infer`` calls do, splits each frame's bytes over its requests, and
    compiles nothing new on either peer."""
    cfg, params, masks, imgs = setup
    plan = _int8_plan(setup, 29866,
                      batching=BatchingPolicy(max_batch=4, max_wait_ms=2.0))
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            singles = [s.infer(im) for im in imgs]
            before = next(iter(srv.lane_stats().values()))
            traces = (s._client._bank.n_traces, srv._engine.bank.n_traces)
            grouped = s.infer_many(imgs)
            assert (s._client._bank.n_traces,
                    srv._engine.bank.n_traces) == traces
            feat = np.asarray(s._client.edge_fn(imgs[0]))
        lane = _lane_delta(srv, before)
        srv.stop()
    assert lane == {"rows": len(imgs), "frames": -(-len(imgs) // 4)}
    for a, b in zip(singles, grouped):
        assert np.array_equal(a["logits"], b["logits"])
    for start in range(0, len(imgs), 4):
        group = grouped[start:start + 4]
        frame = encode_feature(np.zeros((len(group),) + feat.shape[1:],
                                        np.float32), codec="int8")
        sizes = [r["tx_bytes"] for r in group]
        assert sum(sizes) == len(frame) and max(sizes) - min(sizes) <= 1
        assert len({r["t_edge"] for r in group}) == 1


def test_socket_infer_many_groups_by_rows(setup):
    """A 2-row request and singles share a frame up to ``max_batch`` rows;
    a request wider than ``max_batch`` goes alone (past the batcher); the
    answers equal the same requests sent one ``infer`` at a time."""
    imgs = setup[3]
    two = np.concatenate(imgs[:2], axis=0)
    wide = np.concatenate(imgs[2:7], axis=0)         # 5 rows > max_batch 4
    batch = [two, imgs[7], imgs[8], wide, imgs[9], two]
    plan = _int8_plan(setup, 29867,
                      batching=BatchingPolicy(max_batch=4, max_wait_ms=2.0))
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            want = [s.infer(x)["logits"] for x in batch]
            before = next(iter(srv.lane_stats().values()))
            got = s.infer_many(batch)
        lane = _lane_delta(srv, before)
        srv.stop()
    # frames [two, 7, 8], [wide] (not batched), [9, two]
    assert lane == {"rows": 7, "frames": 2}
    assert [r["logits"].shape[0] for r in got] == [2, 1, 1, 5, 1, 2]
    for a, b in zip(want, got):
        assert np.array_equal(a, b["logits"])


@pytest.mark.parametrize("case", ["no_batching", "adaptive", "infer"])
def test_socket_requests_stay_one_frame_each_without_grouping(setup, case):
    """A plan without ``batching``, an adaptive plan's sequential fallback,
    and the synchronous ``infer`` all send one frame per request."""
    from repro.core.collab.adaptive import AdaptivePolicy
    imgs = setup[3][:5]
    kw = {"no_batching": {},
          "adaptive": {"adaptive": AdaptivePolicy(candidates=(SPLIT,)),
                       "batching": BatchingPolicy(max_batch=4)},
          "infer": {"batching": BatchingPolicy(max_batch=4)}}[case]
    plan = _int8_plan(setup, 29868 + ["no_batching", "adaptive",
                                      "infer"].index(case), **kw)
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            encode = s._client._encode_payload
            rows = []

            def counted(xs):
                rows.append(sum(int(x.shape[0]) for x in xs))
                return encode(xs)
            s._client._encode_payload = counted
            out = ([s.infer(im) for im in imgs] if case == "infer"
                   else s.infer_many(imgs))
        srv.stop()
    assert len(out) == len(imgs)
    assert rows == [1] * len(imgs)


def test_infer_batch_handles_multi_row_requests(setup):
    """A request may itself be a multi-row image batch: per-request
    frames and returned logits must carve the fused tensor at the row
    offsets, not one-row-per-request."""
    from repro.core.collab.runtime import CollabRunner
    from repro.core.partition.profiles import PAPER_PROFILE
    cfg, params, masks, imgs = setup
    runner = CollabRunner(params, cfg, SPLIT, PAPER_PROFILE, masks=masks,
                          compact=True, codec="fp32")
    two = np.concatenate([imgs[0], imgs[1]], axis=0)       # (2, H, W, C)
    # the engine is row-mapped: each ROW must match its batch-1 result
    # bitwise (a 2-row request through sequential infer would use a true
    # batch-2 conv, which XLA may legally re-associate)
    singles = [runner.infer(im)["logits"] for im in imgs[:3]]
    got = runner.infer_batch([two, imgs[2]])
    assert got[0]["logits"].shape[0] == 2
    assert got[1]["logits"].shape[0] == 1
    assert np.array_equal(singles[0][0], got[0]["logits"][0])
    assert np.array_equal(singles[1][0], got[0]["logits"][1])
    assert np.array_equal(singles[2], got[1]["logits"])


def test_local_fast_path_and_batched_server_accept_multi_row(setup):
    """Requests wider than one row — and even wider than max_batch —
    must serve on a batching plan exactly like they do without one
    (fast path chunks by ROWS; the server bypasses the engine for
    frames no bucket can hold)."""
    cfg, params, masks, imgs = setup
    wide = np.concatenate(imgs[:5], axis=0)          # 5 rows > max_batch 4
    two = np.concatenate(imgs[:2], axis=0)
    batch = [two, imgs[2], wide, imgs[3]]
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="fp32",
        shape_link=False, port=29862,
        batching=BatchingPolicy(max_batch=4, max_wait_ms=2.0))
    with serving.connect(plan, backend="local") as s:
        res = s.infer_many(batch)
    assert [r["logits"].shape[0] for r in res] == [2, 1, 5, 1]
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            got = [s.infer(x) for x in batch]
        srv.stop()
    assert [r["logits"].shape[0] for r in got] == [2, 1, 5, 1]


def test_local_session_infer_many_fast_path_bit_identical(setup):
    cfg, params, masks, imgs = setup
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        batching=BatchingPolicy(max_batch=4, max_wait_ms=2.0))
    with serving.connect(plan, backend="local") as s:
        seq = [s.infer(im)["logits"] for im in imgs]
    with serving.connect(plan, backend="local") as s:
        fast = s.infer_many(imgs)
    assert len(fast) == len(imgs)
    for a, b in zip(seq, fast):
        assert np.array_equal(a, b["logits"])
    # tx accounting preserved per request on the fused path
    assert all(r["tx_bytes"] > 0 for r in fast)
