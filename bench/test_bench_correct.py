"""The harness's check of ``correct``, driven end to end on the CPU at a
tiny size: a sound run passes, and the control (the program's int4 edge
weights) and a timed path broken underneath (an answer altered where the
cloud produces it) fail. The chip check is skipped: these runs call the
harness below ``main``."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import model, run
from bench.loops import open as open_loop


def _conv(c, k, s, p):
    return {"kind": "conv", "out_channels": c, "kernel": k, "stride": s,
            "padding": p}


R = {"kind": "relu"}
POOL = {"kind": "maxpool", "kernel": 2, "stride": 2}
#: the served path's layer kinds at a size a test holds
TINY = {"name": "tiny", "input_hw": 32, "input_channels": 3,
        "num_classes": 10,
        "layers": [_conv(16, 3, 2, 1), R, POOL, _conv(32, 3, 1, 1), R, POOL,
                   {"kind": "flatten"}, {"kind": "dense", "features": 64}, R,
                   {"kind": "dense", "features": 10}],
        "deployment": {"split": 6, "prune_keep": 0.5, "prune_from_conv": 1,
                       "weight_bits": 8, "codec": "int8", "max_batch": 8,
                       "max_wait_ms": 3.0, "shape_link": False},
        # at this size sound runs read ~0.006 and the int4 control ~0.10
        # (CPU); the limit sits between, as a configuration's does
        "limits": {"logit_err": 0.03}}
LIMIT = TINY["limits"]["logit_err"]
TRAFFIC = {"sat": {"loop": "pipelined", "edges": 3, "chunk": 4, "pool": 8,
                   "warm_s": 0.3},
           "open": {"loop": "open", "edges": 3, "rate_per_s": 80, "pool": 8,
                    "warm_s": 0.3}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(traffic="sat", weight_bits=None):
    return run.run_cell(TINY, TRAFFIC[traffic],
                        [("req_per_s", "req/s"), ("p50_ms", "ms"),
                         ("p95_ms", "ms"), ("setup_s", "s")],
                        seed=2**33 + 17,
                        seconds=1.0, trace=False, limit=LIMIT,
                        t_start=time.perf_counter(), peaks=PEAKS,
                        weight_bits=weight_bits)


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_sound_run_is_correct(traffic):
    res = _run(traffic)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"req_per_s", "setup_s"} <= set(res["metrics"])


def test_control_int4_edge_weights_is_not_correct():
    res = _run(weight_bits=4)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > LIMIT


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    from repro.core.collab import runtime
    step = runtime.split_step

    def altered(cfg, split, side, *a, **kw):
        fn = step(cfg, split, side, *a, **kw)
        if side != "cloud":
            return fn

        def flip_top(p, x):
            y = fn(p, x)
            top = y.argmax(axis=-1)
            return y.at[np.arange(y.shape[0]), top].multiply(-1.0)
        return flip_top

    monkeypatch.setattr(runtime, "split_step", altered)
    res = _run()
    assert not res["correct"]
    assert res["failed"] == 0
    assert res["checks"]["logit_err"]["value"] > LIMIT


def test_half_of_each_fused_batch_left_out_is_not_correct(monkeypatch):
    """The batched cloud step computes only the first half of its rows and
    hands their answers to the other half too."""
    import jax.numpy as jnp

    from repro.core.collab import runtime
    step = runtime.split_step

    def halved(cfg, split, side, *a, **kw):
        fn = step(cfg, split, side, *a, **kw)
        if side != "cloud":
            return fn

        def first_half(p, x):
            n = x.shape[0]
            if n < 2:
                return fn(p, x)
            y = fn(p, x[:(n + 1) // 2])
            return jnp.concatenate([y, y[:n - y.shape[0]]])
        return first_half

    monkeypatch.setattr(runtime, "split_step", halved)
    res = _run()
    assert not res["correct"]
    assert res["failed"] == 0
    assert res["checks"]["logit_err"]["value"] > LIMIT


def test_open_loop_offers_the_same_gaps_for_every_seed():
    a = open_loop.offsets(400, 5, model.seed_rng(1, 2))
    b = open_loop.offsets(400, 5, model.seed_rng(2**40, 2))
    assert len(a) == len(b) == 2000

    def gaps(t):         # the first gap is the one before the last due time
        return np.append(np.diff(t), 5 - t[-1])
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(b)))
    assert not np.allclose(gaps(a), gaps(b))
    assert a[0] == 0 and a[-1] < 5


def test_every_request_image_is_distinct():
    pool = np.random.default_rng(0).random((4, 2, 2, 3), np.float32)
    order = np.arange(4)
    imgs = {model.request_image(pool, order, i).tobytes()
            for i in range(-8, 40)}
    assert len(imgs) == 48


def test_traced_run_reads_per_layer_metrics(monkeypatch):
    """A traced run reports its per-layer metrics and the device's busy and
    window seconds; the trace's reduction is stubbed, since the CPU has no
    device plane to reduce."""
    from bench import trace as tracefile
    monkeypatch.setattr(tracefile, "read", lambda path: tracefile.TraceSummary(
        window_s=1.0, busy_s=0.25, n_devices=1))
    res = run.run_cell(TINY, TRAFFIC["sat"],
                       [("batch_rows.sat", "rows"), ("mfu.sat", "%"),
                        ("device_idle.sat", "%")],
                       seed=2**33 + 19, seconds=2.0, trace=True, limit=LIMIT,
                       t_start=time.perf_counter(), peaks=PEAKS)
    assert res["correct"], res["checks"]
    assert res["device"]["busy_s"] == 0.25
    assert res["device"]["window_s"] == 1.0
    assert res["metrics"]["device_idle.sat"]["value"] == pytest.approx(75.0)
    assert 1 <= res["metrics"]["batch_rows.sat"]["value"] <= 8
    assert res["metrics"]["mfu.sat"]["value"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
