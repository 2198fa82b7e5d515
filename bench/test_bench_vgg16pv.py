"""The ``vgg16pv`` configuration on the CPU: VGG's layer pattern (stages of
stacked 3x3/s1/p1 convs, 2x2 pools, the split after pool5, a 38-way head)
served through the harness at a tiny size is ``correct`` and its int4
control is not; the operation and byte counts of the published widths
match the hand figures; and ``edge_outside_kernel_ms`` reads the recorded
``alexnet224.sat`` trace and is absent where no edge program ran."""
from __future__ import annotations

import os
import time

import pytest

from bench import costs, model, run, trace
from bench.metrics import edge_device_ms, edge_outside_kernel_ms
from bench.test_bench_correct import PEAKS, POOL, R, TRAFFIC, _conv

HERE = os.path.dirname(os.path.abspath(__file__))


def _stage(width, convs):
    return [x for _ in range(convs) for x in (_conv(width, 3, 1, 1), R)] + [
        POOL]


#: VGG-16-D's pattern at 32 px: 13 convs in stages 2-2-3-3-3, five 2x2
#: pools down to 1x1, two hidden dense layers and PlantVillage's 38-way
#: head; c = 31 is after pool5, as in ``vgg16pv``
TINY_VGG = {"name": "tiny_vgg", "input_hw": 32, "input_channels": 3,
            "num_classes": 38,
            "layers": (_stage(8, 2) + _stage(16, 2) + _stage(32, 3)
                       + _stage(64, 3) + _stage(64, 3)
                       + [{"kind": "flatten"},
                          {"kind": "dense", "features": 64}, R,
                          {"kind": "dense", "features": 64}, R,
                          {"kind": "dense", "features": 38}]),
            "deployment": {"split": 31, "prune_keep": 0.5,
                           "prune_from_conv": 1, "weight_bits": 8,
                           "codec": "int8", "max_batch": 8,
                           "max_wait_ms": 3.0, "shape_link": False},
            # on the CPU sound runs read 0.003-0.010 over five seeds
            # (0.008-0.010 at this file's seed) and the int4 control
            # 0.024-0.088 (0.049 here); the limit sits between, 2x above
            # the sound readings here and 2.5x below the control's
            "limits": {"logit_err": 0.02}}
LIMIT = TINY_VGG["limits"]["logit_err"]


def _run(traffic="sat", weight_bits=None):
    return run.run_cell(TINY_VGG, TRAFFIC[traffic],
                        [("req_per_s", "req/s"), ("setup_s", "s")],
                        seed=2**33 + 29, seconds=1.0, trace=False,
                        limit=LIMIT, t_start=time.perf_counter(),
                        peaks=PEAKS, weight_bits=weight_bits)


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_sound_vgg_pattern_run_is_correct(traffic):
    res = _run(traffic)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_vgg_pattern_int4_control_is_not_correct():
    res = _run(weight_bits=4)
    assert not res["correct"]
    assert res["failed"] == 0
    assert res["checks"]["logit_err"]["value"] > LIMIT


def test_vgg16pv_is_vgg16_with_the_38_class_head():
    pv, d = model.load_config("vgg16pv"), model.load_config("vgg16")
    assert pv["num_classes"] == 38 and pv["layers"][-1]["features"] == 38
    assert pv["layers"][:-1] == d["layers"][:-1]
    for key in ("input_hw", "input_channels", "deployment", "precision",
                "source", "reduced"):
        assert pv[key] == d[key], key
    assert "38" in pv["assumed"]["head"]


def test_vgg16pv_operation_and_byte_counts_match_the_hand_figures():
    """Per request at deployed widths: edge 8.728 GFLOP, cloud 0.1366
    GFLOP, 273.3 MB of float32 cloud weights a row, 0.224 GB of float32
    im2col patches, and a masked_matmul floor of 0.319 ms a forward, every
    GEMM bound by memory."""
    cfg = model.load_config("vgg16pv")
    flops = costs.split_flops(cfg)
    assert flops["edge"] / 1e9 == pytest.approx(8.728, rel=1e-3)
    assert flops["cloud"] / 1e9 == pytest.approx(0.1366, rel=1e-3)
    assert costs.cloud_weight_bytes(cfg) / 1e6 == pytest.approx(273.3,
                                                                 rel=1e-3)
    gemms = costs.edge_gemms(cfg)
    assert sum(m * k * 4 for m, k, _ in gemms) / 1e9 == pytest.approx(
        0.224, rel=1e-3)
    floors = [costs.masked_matmul_floor_s(m, k, n, 197e12, 819e9)
              for m, k, n in gemms]
    assert sum(t for t, _ in floors) * 1e3 == pytest.approx(0.319, rel=1e-3)
    assert {b for _, b in floors} == {"memory"}
    assert max(gemms) == (50176, 576, 32)             # conv1_2


def test_edge_outside_kernel_reads_the_recorded_trace():
    s = trace.read(os.path.join(HERE, "testdata",
                                "trace_alexnet224_sat.xplane.pb"))
    outside = edge_outside_kernel_ms.read({"trace": s})
    assert 0 < outside < edge_device_ms.read({"trace": s})


@pytest.mark.parametrize("summary", [
    None,
    trace.TraceSummary(window_s=1.0, busy_s=0.25, n_devices=1),
    trace.reduce_events({"/device:TPU:0": [
        ("jit_cloud_c31_rows(1)/%while", 10, 5),
        ("jit_traced(2)/%masked_matmul.1", 20, 5)]},
        [("bench.trace_window", 0, 100)]),
], ids=["no_trace", "empty", "no_edge_module"])
def test_edge_outside_kernel_is_absent_without_an_edge_module(summary):
    assert edge_outside_kernel_ms.read({"trace": summary}) is None
