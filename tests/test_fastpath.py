"""Fast deployment path: compaction everywhere, feature codec, pipelined
streaming runtime, async socket client, exact-read framing."""
from __future__ import annotations

import socket
import struct
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.collab.channel import recv_exact
from repro.core.collab.protocol import (CODEC_TX_SCALE, FEATURE_MAGIC,
                                        affine_quantize, decode_any,
                                        decode_feature, encode_feature,
                                        encode_tensor, frame_lane)
from repro.core.collab.runtime import (CollabRunner, EdgeClient,
                                       deploy_submodels, serve_cloud)
from repro.core.collab.streaming import StreamingCollabRunner
from repro.core.partition.latency_model import (cnn_input_bytes,
                                                cnn_layer_costs,
                                                compacted_cnn_layer_costs)
from repro.core.partition.profiles import PAPER_PROFILE
from repro.core.partition.splitter import greedy_split
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.models.cnn import (cnn_apply, compact_cnn_config, compact_params,
                              init_cnn_params, prunable_layers,
                              split_keep_indices, tiny_cnn_config)


@pytest.fixture(scope="module")
def pruned_setup():
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    masks = cnn_masks_from_ratios(
        params, cfg, {i: 0.5 for i in prunable_layers(cfg)})
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3)),
                   np.float32)
    want = np.asarray(cnn_apply(params, cfg, x, masks=masks))
    return cfg, params, masks, x, want


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------
def test_compacted_split_matches_masked_every_split(pruned_setup):
    """Acceptance: compacted split inference == masked logits (1e-4) at
    EVERY split point of tiny_cnn_config."""
    cfg, params, masks, x, want = pruned_setup
    cparams, ccfg = compact_params(params, cfg, masks)
    for c in range(len(cfg.layers) + 1):
        mid = cnn_apply(cparams, ccfg, jnp.asarray(x), stop_layer=c)
        out = np.asarray(cnn_apply(cparams, ccfg, mid, start_layer=c))
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"split {c}")


def test_compact_cnn_config_matches_materialized(pruned_setup):
    cfg, params, masks, _, _ = pruned_setup
    _, ccfg = compact_params(params, cfg, masks)
    assert compact_cnn_config(cfg, masks) == ccfg


def test_collab_runner_compact(pruned_setup):
    cfg, params, masks, x, want = pruned_setup
    runner = CollabRunner(params, cfg, 6, PAPER_PROFILE, masks=masks,
                          compact=True)
    res = runner.infer(x)
    np.testing.assert_allclose(res["logits"], want, rtol=1e-4, atol=1e-4)
    # compacted deployment ships only surviving channels
    dense = CollabRunner(params, cfg, 6, PAPER_PROFILE, masks=masks)
    assert res["timing"].tx_bytes < dense.infer(x)["timing"].tx_bytes


def test_deploy_submodels_shapes(pruned_setup):
    cfg, params, masks, _, _ = pruned_setup
    dparams, dcfg, dmasks = deploy_submodels(params, cfg, masks,
                                             compact=True)
    assert dmasks is None
    w = dparams["l0"]["w"]
    assert w.shape[-1] == int(np.asarray(masks[0]).sum())
    assert dcfg.layers[0].out_channels == w.shape[-1]


def test_compacted_costs_price_smaller_model(pruned_setup):
    cfg, params, masks, _, _ = pruned_setup
    dense = cnn_layer_costs(cfg)
    compacted = compacted_cnn_layer_costs(cfg, masks)
    assert sum(c.flops for c in compacted) < 0.6 * sum(c.flops
                                                       for c in dense)
    # masked analytic pricing and compacted pricing agree (masks are 0/1)
    masked = cnn_layer_costs(cfg, masks)
    for a, b in zip(masked, compacted):
        assert a.flops == pytest.approx(b.flops, rel=1e-6)
        assert a.out_bytes == pytest.approx(b.out_bytes, rel=1e-6)


def test_greedy_split_tx_scale_discounts_transmission(pruned_setup):
    cfg, params, masks, _, _ = pruned_setup
    costs = compacted_cnn_layer_costs(cfg, masks)
    full = greedy_split(costs, PAPER_PROFILE, cnn_input_bytes(cfg))
    disc = greedy_split(costs, PAPER_PROFILE, cnn_input_bytes(cfg),
                        tx_scale=CODEC_TX_SCALE["int8"])
    for c_full, c_disc in zip(full.table, disc.table):
        assert c_disc["T_TX"] <= c_full["T_TX"] + 1e-12
        assert c_disc["T_D"] == c_full["T_D"]


# ---------------------------------------------------------------------------
# feature codec
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", ["fp32", "fp16", "int8"])
def test_codec_roundtrip(codec):
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 4, 24).astype(np.float32)
    buf = encode_feature(x, codec=codec)
    out, used = decode_feature(buf)
    assert used == len(buf)
    assert out.shape == x.shape and out.dtype == np.float32
    if codec == "fp32":
        np.testing.assert_array_equal(out, x)
    elif codec == "fp16":
        np.testing.assert_allclose(out, x, rtol=1e-3, atol=1e-3)
    else:
        scale = (x.max() - x.min()) / 255.0
        assert np.abs(out - x).max() <= scale / 2 + 1e-6


def test_codec_packed_roundtrip_restores_zeros():
    rng = np.random.RandomState(1)
    keep = np.array([1, 5, 6, 10, 23])
    x = np.zeros((2, 3, 3, 24), np.float32)
    x[..., keep] = rng.randn(2, 3, 3, keep.size)
    for codec in ("fp32", "fp16", "int8"):
        buf = encode_feature(x, codec=codec, keep=keep)
        out, _ = decode_feature(buf)
        dead = np.setdiff1d(np.arange(24), keep)
        assert (out[..., dead] == 0).all()
        tol = {"fp32": 1e-7, "fp16": 1e-3, "int8": 0.05}[codec]
        np.testing.assert_allclose(out[..., keep], x[..., keep],
                                   rtol=tol, atol=tol)
    # packed int8 beats raw fp32 by > the keep fraction alone
    raw = len(encode_tensor(x))
    packed = len(encode_feature(x, codec="int8", keep=keep))
    assert packed < 0.25 * raw


def _single_pair_int8_frame(x, keep=None):
    """An int8 feature frame as one (scale, zero) pair for the whole
    tensor encodes it: the layout of a one-row frame."""
    shape = x.shape
    if keep is not None:
        x = np.ascontiguousarray(x[..., keep])
    q, scale, zero = affine_quantize(x, levels=255)
    mask = b""
    if keep is not None:
        bits = np.zeros(shape[-1], np.uint8)
        bits[keep] = 1
        mask = np.packbits(bits).tobytes()
    return (struct.pack("<IBBH", FEATURE_MAGIC, 2, int(keep is not None),
                        len(shape))
            + struct.pack(f"<{len(shape)}Q", *shape) + mask
            + struct.pack("<ff", scale, zero)
            + struct.pack("<Q", q.nbytes) + q.tobytes())


@pytest.mark.parametrize("shape,packed", [((1, 6, 6, 24), False),
                                          ((1, 6, 6, 24), True),
                                          ((1, 37), False), ((37,), False)])
def test_int8_one_row_frame_is_the_single_pair_encoding(shape, packed):
    rng = np.random.RandomState(3)
    keep = np.array([0, 3, 4, 20]) if packed else None
    for seed in range(20):
        x = (rng.randn(*shape) * 10.0 ** rng.randint(-3, 4)
             + rng.randn()).astype(np.float32)
        assert encode_feature(x, codec="int8", keep=keep) == \
            _single_pair_int8_frame(x, keep), seed
    flat = np.full(shape, 2.5, np.float32)           # degenerate range
    assert encode_feature(flat, codec="int8") == _single_pair_int8_frame(flat)


@pytest.mark.parametrize("packed", [False, True])
def test_int8_rows_of_a_multi_row_frame_decode_as_their_own_frames(packed):
    """Each row of a k-row int8 frame carries exactly the codes and
    (scale, zero) of its own one-row frame, so it decodes bitwise alike
    however it is grouped; both frames keep the ``int8`` lane."""
    rng = np.random.RandomState(4)
    keep = np.array([1, 5, 6, 10, 23]) if packed else None
    # rows of very different ranges, one of them constant
    x = np.stack([rng.randn(6, 6, 24) * s for s in (1e-3, 1.0, 50.0, 0.0)]
                 ).astype(np.float32) + np.float32(0.25)
    frame = encode_feature(x, codec="int8", keep=keep)
    ones = [encode_feature(x[i:i + 1], codec="int8", keep=keep)
            for i in range(len(x))]
    got, used = decode_feature(frame)
    assert used == len(frame)
    for i, one in enumerate(ones):
        want, _ = decode_feature(one)
        assert got[i:i + 1].tobytes() == want.tobytes(), i
    # the header's pairs and the payload's codes are the rows' own
    at = _pair_offset(ones[0])
    pairs = [o[at:at + 8] for o in ones]
    codes = [o[at + 16:] for o in ones]         # after the pair and nbytes
    assert frame[at:at + 8 * len(x)] == b"".join(pairs)
    assert frame[at + 8 * len(x) + 8:] == b"".join(codes)
    assert frame_lane(frame) == frame_lane(ones[0]) == (
        "int8+packed" if packed else "int8")


def _pair_offset(one_row_frame):
    """Where the (scale, zero) pair starts in a one-row int8 frame: after
    the fixed header, the shape and any keep bitmask."""
    ndim = struct.unpack_from("<H", one_row_frame, 6)[0]
    packed = one_row_frame[5]
    shape = struct.unpack_from(f"<{ndim}Q", one_row_frame, 8)
    return 8 + 8 * ndim + ((shape[-1] + 7) // 8 if packed else 0)


def test_decode_any_dispatches_both_frames():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    for buf in (encode_tensor(x), encode_feature(x, codec="fp32")):
        out, used = decode_any(buf)
        assert used == len(buf)
        np.testing.assert_array_equal(out, x)


def test_split_keep_indices_marks_only_live_channels(pruned_setup):
    cfg, params, masks, x, _ = pruned_setup
    for c in range(1, len(cfg.layers) + 1):
        keep = split_keep_indices(cfg, masks, c)
        act = np.asarray(cnn_apply(params, cfg, jnp.asarray(x),
                                   masks=masks, stop_layer=c))
        if keep is None:
            continue
        dead = np.setdiff1d(np.arange(act.shape[-1]), keep)
        assert (act[..., dead] == 0).all(), f"split {c}"


def test_collab_runner_packed_codec_lossless_fp32(pruned_setup):
    """fp32 + channel packing is bit-preserving end-to-end."""
    cfg, params, masks, x, want = pruned_setup
    runner = CollabRunner(params, cfg, 4, PAPER_PROFILE, masks=masks,
                          codec="fp32", pack=True)
    res = runner.infer(x)
    np.testing.assert_allclose(res["logits"], want, rtol=1e-5, atol=1e-5)
    dense = CollabRunner(params, cfg, 4, PAPER_PROFILE, masks=masks)
    assert res["timing"].tx_bytes < dense.infer(x)["timing"].tx_bytes


# ---------------------------------------------------------------------------
# pipelined streaming runtime
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw", [dict(),
                                dict(compact=True),
                                dict(compact=True, microbatch=4),
                                dict(codec="fp32", pack=True)])
def test_streaming_matches_sequential(pruned_setup, kw):
    cfg, params, masks, x, _ = pruned_setup
    imgs = [x[i % 2:i % 2 + 1] for i in range(8)]
    seq = CollabRunner(params, cfg, 6, PAPER_PROFILE, masks=masks,
                       **{k: v for k, v in kw.items() if k != "microbatch"})
    pipe = StreamingCollabRunner(params, cfg, 6, PAPER_PROFILE, masks=masks,
                                 realtime_channel=False, **kw)
    rep = pipe.run(imgs)
    assert len(rep.results) == len(imgs)
    for img, got in zip(imgs, rep.results):
        want = seq.infer(img)["logits"]
        np.testing.assert_allclose(got["logits"], want, rtol=1e-4,
                                   atol=1e-4)
    assert rep.throughput_rps > 0
    assert set(rep.occupancy) == {"edge", "tx", "cloud"}
    assert all(0.0 <= v for v in rep.occupancy.values())


def test_streaming_edge_only_and_cloud_only(pruned_setup):
    cfg, params, masks, x, _ = pruned_setup
    n = len(cfg.layers)
    imgs = [x[:1]] * 3
    for split in (0, n):
        pipe = StreamingCollabRunner(params, cfg, split, PAPER_PROFILE,
                                     masks=masks, realtime_channel=False)
        rep = pipe.run(imgs)
        want = np.asarray(cnn_apply(params, cfg, imgs[0], masks=masks))
        np.testing.assert_allclose(rep.results[0]["logits"], want,
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# socket path: exact reads, compacted service, async pipelining
# ---------------------------------------------------------------------------
def test_recv_exact_reassembles_dribbled_stream():
    a, b = socket.socketpair()
    payload = bytes(range(256)) * 50

    def dribble():
        for i in range(0, len(payload), 97):
            a.sendall(payload[i:i + 97])
        a.close()

    t = threading.Thread(target=dribble)
    t.start()
    got = recv_exact(b, len(payload), chunk=64)
    t.join()
    assert got == payload
    with pytest.raises(EOFError):
        recv_exact(b, 1)
    b.close()


def test_socket_compact_int8_roundtrip(pruned_setup):
    cfg, params, masks, x, want = pruned_setup
    split, port = 6, 29491
    ready = threading.Event()
    srv = threading.Thread(target=serve_cloud,
                           args=(params, cfg, split, port),
                           kwargs=dict(masks=masks, max_requests=2,
                                       ready=ready, compact=True),
                           daemon=True)
    srv.start()
    assert ready.wait(10)
    client = EdgeClient(params, cfg, split, port, masks=masks,
                        compact=True, codec="int8")
    for _ in range(2):
        res = client.infer(x)
        np.testing.assert_allclose(res["logits"], want, rtol=0.05,
                                   atol=0.05)
    client.close()
    srv.join(10)
    assert not srv.is_alive()


def test_edge_client_submit_collect_pipelined(pruned_setup):
    """Async submit/collect returns the same logits as sync infer, in
    submission order."""
    cfg, params, masks, x, want = pruned_setup
    split, port = 6, 29492
    n_req = 6
    ready = threading.Event()
    srv = threading.Thread(target=serve_cloud,
                           args=(params, cfg, split, port),
                           kwargs=dict(masks=masks, max_requests=n_req,
                                       ready=ready, compact=True),
                           daemon=True)
    srv.start()
    assert ready.wait(10)
    client = EdgeClient(params, cfg, split, port, masks=masks,
                        compact=True)
    imgs = [x[i % 2:i % 2 + 1] for i in range(n_req)]
    wants = [np.asarray(cnn_apply(params, cfg, img, masks=masks))
             for img in imgs]
    for img in imgs:
        client.submit(img)
    first = client.collect(2)          # partial collect, then the rest
    results = first + client.collect()
    assert len(results) == n_req
    for res, w in zip(results, wants):
        np.testing.assert_allclose(res["logits"], w, rtol=1e-4, atol=1e-4)
        assert res["tx_bytes"] > 0
    client.close()
    srv.join(10)
    assert not srv.is_alive()
