"""Unified serving surface: DeploymentPlan artifact (digest, save/load),
serving.connect backends (local / socket / streaming — same plan, same
logits), the HELLO contract handshake, and multi-client serve_cloud."""
from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from repro import serving
from repro.core.collab.runtime import EdgeClient, deploy_submodels
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.models.cnn import (cnn_apply, init_cnn_params, prunable_layers,
                              tiny_cnn_config)

SPLIT = 6       # interior split: a real edge + cloud pair


@pytest.fixture(scope="module")
def plan_setup():
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    masks = cnn_masks_from_ratios(
        params, cfg, {i: 0.5 for i in prunable_layers(cfg)})
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3)),
                   np.float32)
    want = np.asarray(cnn_apply(params, cfg, x, masks=masks))
    return cfg, params, masks, x, want


def make_plan(plan_setup, port=29510, **kw):
    cfg, params, masks, _, _ = plan_setup
    kw.setdefault("split", SPLIT)
    kw.setdefault("masks", masks)
    kw.setdefault("compact", True)
    kw.setdefault("codec", "fp32")
    kw.setdefault("shape_link", False)
    return serving.DeploymentPlan.from_args(params, cfg, port=port, **kw)


# ---------------------------------------------------------------------------
# the plan artifact
# ---------------------------------------------------------------------------
def test_plan_digest_stable_and_contract_sensitive(plan_setup):
    a, b = make_plan(plan_setup), make_plan(plan_setup)
    assert a.digest == b.digest                      # deterministic
    assert a.digest != make_plan(plan_setup, split=SPLIT - 1).digest
    assert a.digest != make_plan(plan_setup, codec="int8").digest
    assert a.digest != make_plan(plan_setup, compact=False).digest
    # transport details are NOT part of the contract
    assert a.digest == make_plan(plan_setup, port=31000).digest


def make_plan_with_split(plan_setup, split, **kw):
    cfg, params, masks, _, _ = plan_setup
    return serving.DeploymentPlan.from_args(params, cfg, split, masks=masks,
                                            compact=True, **kw)


def test_plan_validation(plan_setup):
    cfg, params, _, _, _ = plan_setup
    with pytest.raises(ValueError, match="compact"):
        serving.DeploymentPlan.from_args(params, cfg, SPLIT, compact=True)
    with pytest.raises(ValueError, match="codec"):
        make_plan(plan_setup, codec="fp64")
    with pytest.raises(ValueError, match="split"):
        make_plan_with_split(plan_setup, len(cfg.layers) + 1)


def test_plan_auto_split_is_greedy_optimum(plan_setup):
    cfg, params, masks, _, _ = plan_setup
    plan = serving.DeploymentPlan.from_args(params, cfg, None, masks=masks,
                                            compact=True, codec="int8")
    assert 0 <= plan.split <= len(cfg.layers)


def test_plan_save_load_roundtrip_serves_identically(plan_setup, tmp_path):
    """Acceptance: a plan saved to disk and re-loaded serves without the
    original pipeline objects, logits bit-identical to in-memory deploy."""
    _, _, _, x, want = plan_setup
    plan = make_plan(plan_setup)
    in_mem = serving.connect(plan, backend="local").infer(x)
    path = plan.save(str(tmp_path / "deploy"))
    loaded = serving.DeploymentPlan.load(path)
    assert loaded.digest == plan.digest
    assert loaded.host == plan.host and loaded.port == plan.port
    out = serving.connect(loaded, backend="local").infer(x)
    np.testing.assert_array_equal(out["logits"], in_mem["logits"])
    np.testing.assert_allclose(out["logits"], want, rtol=1e-4, atol=1e-4)


def test_plan_load_rejects_tampered_contract(plan_setup, tmp_path):
    import json
    import os
    plan = make_plan(plan_setup)
    path = plan.save(str(tmp_path / "deploy"))
    doc = json.load(open(os.path.join(path, "plan.json")))
    doc["split"] = SPLIT - 1                      # edit the contract
    json.dump(doc, open(os.path.join(path, "plan.json"), "w"))
    with pytest.raises(ValueError, match="digest"):
        serving.DeploymentPlan.load(path)


# ---------------------------------------------------------------------------
# one contract, three backends
# ---------------------------------------------------------------------------
def test_three_backends_bit_identical_logits(plan_setup):
    """Acceptance: local / socket / streaming through serving.connect
    return bit-identical logits for the same plan."""
    _, _, _, x2, want2 = plan_setup
    x, want = x2[:1], want2[:1]        # streaming requests are batch-1
    plan = make_plan(plan_setup, port=29511)
    local = serving.connect(plan, backend="local").infer(x)
    np.testing.assert_allclose(local["logits"], want, rtol=1e-4, atol=1e-4)

    stream_sess = serving.connect(plan, backend="streaming",
                                  realtime_channel=False)
    stream = stream_sess.infer(x)
    np.testing.assert_array_equal(stream["logits"], local["logits"])

    with serving.CloudServer(plan):
        with serving.connect(plan, backend="socket") as sess:
            sock = sess.infer(x)
    np.testing.assert_array_equal(sock["logits"], local["logits"])

    for res in (local, stream, sock):      # uniform result shape
        assert set(res) == {"logits", "t_edge", "t_upstream", "t_total",
                            "tx_bytes", "e_edge_j", "fault"}
        assert res["e_edge_j"] is None     # un-metered plan: no joules
        # uniform fault accounting: all-zero on a clean request
        assert res["fault"] == {"faults": 0, "retries": 0,
                                "migrations": 0, "fallback": False}


def test_streaming_backend_reports_pipeline_stats(plan_setup):
    _, _, _, x, _ = plan_setup
    plan = make_plan(plan_setup)
    sess = serving.connect(plan, backend="streaming",
                           realtime_channel=False)
    out = sess.infer_many([x[:1]] * 4)
    assert len(out) == 4
    rep = sess.last_report
    assert rep.throughput_rps > 0
    assert set(rep.occupancy) == {"edge", "tx", "cloud"}


def test_socket_backend_pipelined_infer_many(plan_setup):
    _, _, _, x, want = plan_setup
    plan = make_plan(plan_setup, port=29512)
    imgs = [x[i % 2:i % 2 + 1] for i in range(5)]
    wants = [want[i % 2:i % 2 + 1] for i in range(5)]
    with serving.CloudServer(plan):
        with serving.connect(plan, backend="socket") as sess:
            out = sess.infer_many(imgs)
    for res, w in zip(out, wants):
        np.testing.assert_allclose(res["logits"], w, rtol=1e-4, atol=1e-4)
        assert res["tx_bytes"] > 0


# ---------------------------------------------------------------------------
# HELLO handshake: contract agreement enforced at connect time
# ---------------------------------------------------------------------------
def test_handshake_digest_mismatch_fails_fast(plan_setup):
    """Acceptance: a deliberate peer plan mismatch errors at connect
    instead of decoding garbage tensors mid-stream."""
    plan = make_plan(plan_setup, port=29513)
    other = make_plan(plan_setup, port=29513, split=SPLIT - 2)
    assert plan.digest != other.digest
    # max_clients=1: a rejected peer must NOT consume the client budget
    with serving.CloudServer(plan, max_clients=1):
        with pytest.raises(serving.PlanMismatchError, match="digest"):
            serving.connect(other, backend="socket")
        # the server survives a rejected peer: a matching edge still works
        with serving.connect(plan, backend="socket") as sess:
            res = sess.infer(plan_setup[3])
            assert res["tx_bytes"] > 0


def test_serve_cloud_survives_connect_and_drop(plan_setup):
    """A probe that connects and closes without a request must not consume
    the bounded server's client budget."""
    import socket as socketlib
    plan = make_plan(plan_setup, port=29516)
    with serving.CloudServer(plan, max_clients=1):
        probe = socketlib.create_connection(("127.0.0.1", 29516))
        probe.close()
        with serving.connect(plan, backend="socket") as sess:
            assert sess.infer(plan_setup[3])["tx_bytes"] > 0


def test_cloud_server_start_surfaces_serve_thread_error(plan_setup):
    """Startup waits for the listener as long as the serve thread lives
    (a cold warm-up may compile for minutes), and a thread that dies
    first is reported with its own error, not a timeout."""
    import socket as socketlib
    with socketlib.socket() as taken:
        taken.bind(("127.0.0.1", 0))
        taken.listen(1)
        plan = make_plan(plan_setup, port=taken.getsockname()[1])
        with pytest.raises(RuntimeError, match="before listening") as err:
            serving.CloudServer(plan)
    assert isinstance(err.value.__cause__, OSError)


def test_handshake_skipped_for_legacy_edge(plan_setup):
    """An edge that never sends HELLO (verify=False) is served unchecked —
    back-compat with pre-plan clients."""
    _, _, _, x, want = plan_setup
    plan = make_plan(plan_setup, port=29514)
    with serving.CloudServer(plan):
        with serving.connect(plan, backend="socket", verify=False) as sess:
            np.testing.assert_allclose(sess.infer(x)["logits"], want,
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# multi-client cloud
# ---------------------------------------------------------------------------
def test_serve_cloud_multi_client_concurrent_edges(plan_setup):
    """Acceptance: one cloud process serves two concurrent edges with
    interleaved requests, each getting its own correct results."""
    _, _, _, x, want = plan_setup
    plan = make_plan(plan_setup, port=29515)
    with serving.CloudServer(plan, max_clients=None):
        s1 = serving.connect(plan, backend="socket")
        s2 = serving.connect(plan, backend="socket")
        errs = []

        def hammer(sess, img, w, n=4):
            try:
                for _ in range(n):
                    np.testing.assert_allclose(
                        sess.infer(img)["logits"], w, rtol=1e-4, atol=1e-4)
            except Exception as e:                        # noqa: BLE001
                errs.append(e)

        t1 = threading.Thread(target=hammer, args=(s1, x[:1], want[:1]))
        t2 = threading.Thread(target=hammer, args=(s2, x[1:], want[1:]))
        t1.start(); t2.start(); t1.join(20); t2.join(20)
        s1.close(); s2.close()
        assert not errs, errs


# ---------------------------------------------------------------------------
# wire accounting: payload parity across backends, analytic == measured
# ---------------------------------------------------------------------------
def test_tx_bytes_payload_identical_across_backends(plan_setup):
    """Acceptance: the same plan reports the same tx_bytes on every
    backend — payload bytes only, excluding the socket path's 8-byte
    length prefix (the historical +8 discrepancy)."""
    _, _, _, x2, _ = plan_setup
    x = x2[:1]
    plan = make_plan(plan_setup, port=29517)
    local = serving.connect(plan, backend="local").infer(x)
    stream = serving.connect(plan, backend="streaming",
                             realtime_channel=False).infer(x)
    with serving.CloudServer(plan):
        with serving.connect(plan, backend="socket") as sess:
            sock = sess.infer(x)
    assert local["tx_bytes"] > 0
    assert local["tx_bytes"] == sock["tx_bytes"] == stream["tx_bytes"]


@pytest.mark.parametrize("codec,pack,compact", [
    ("fp32", False, True), ("fp16", False, True), ("int8", False, True),
    ("fp32", True, False), ("int8", True, False), ("fp32", False, False),
])
def test_analytic_tx_bytes_matches_measured_payload(plan_setup, codec,
                                                    pack, compact):
    """The re-priced tx_scale (codec x packing, ``wire_tx_scale``) makes
    the analytic Eq. 5 tx_bytes agree with the measured frame payload —
    including the masked-but-dense unpacked case, which ships the full
    tensor (zeros included)."""
    from repro.core.collab.runtime import CollabRunner
    cfg, params, masks, x, _ = plan_setup
    runner = CollabRunner(params, cfg, SPLIT, serving.DeploymentPlan(
        cfg=cfg, params=params, split=SPLIT).profile, masks=masks,
        compact=compact, codec=codec, pack=pack)
    measured = runner.infer(x[:1])["timing"].tx_bytes
    analytic = runner._analytic["tx_bytes"]
    # frame headers (magic/shape/bitmask/quant params) are not modelled:
    # allow tens of bytes, not the ~KBs a keep-ratio mistake would cause
    assert abs(measured - analytic) <= 160, (codec, pack, compact,
                                             measured, analytic)


# ---------------------------------------------------------------------------
# satellites: deploy_submodels guard, EdgeClient host/timeout
# ---------------------------------------------------------------------------
def test_deploy_submodels_compact_without_masks_raises(plan_setup):
    cfg, params, _, _, _ = plan_setup
    with pytest.raises(ValueError, match="compact"):
        deploy_submodels(params, cfg, masks=None, compact=True)
    with pytest.raises(ValueError, match="compact"):
        deploy_submodels(params, cfg, masks={}, compact=True)


def test_edge_client_accepts_host_and_timeout(plan_setup):
    cfg, params, _, _, _ = plan_setup
    with pytest.raises(OSError):
        # unroutable TEST-NET address: proves host/timeout are honoured
        # (fails fast instead of the old hardwired 127.0.0.1 / 30 s)
        EdgeClient(params, cfg, SPLIT, 29599, host="192.0.2.1",
                   timeout=0.2)
