"""Quantized Pallas edge path: the differential campaign.

Three contracts pinned here (see docs/quantized-edge.md):

  1. **bit-identity** — with ``weight_bits=None`` the kernel dispatch
     changes only *how* the GEMMs run, not what they compute: the
     Pallas kernel (interpret mode, whole-array blocks) and the
     pure-XLA ``ref`` twin agree bit-for-bit at EVERY candidate split
     boundary;
  2. **bounded error** — int8/int4 per-channel weight quantization errs
     by at most ``gemm_error_bound`` per layer (the affine codec's
     ``scale/2`` contract times the input's L1 norm), and the
     end-to-end logits stay close to fp32;
  3. **one contract, three backends** — a plan carrying a ``quant``
     section serves bit-identical logits through local / socket /
     streaming ``serving.connect``, survives save/load, and folds the
     section into the digest only when set.

Plus the kernel-cost calibration hook (``calibrate_quant_edge`` ->
``sweep_splits(measured_device_s=...)``), the MCU/Pi roofline check,
and a golden-numerics regression file so the quantized forward's
numerics cannot drift silently between commits.

Hypothesis property tests ride along when hypothesis is installed; the
deterministic campaign below never skips.
"""
from __future__ import annotations

import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.configs.base import CNNConfig, ConvLayerSpec
from repro.core.collab import quant
from repro.core.collab.protocol import affine_quantize
from repro.core.collab.quant import (BITS_LEVELS, KERNEL_BLOCK_K,
                                     QuantPolicy, calibrate_quant_edge,
                                     conv_weight_gemm_layout,
                                     dequantize_weights, gemm_error_bound,
                                     im2col, quant_cnn_apply, quantize_params,
                                     quantize_weights, resolve_backend)
from repro.core.partition.latency_model import (KernelCalibration,
                                                cnn_input_bytes,
                                                quantized_cnn_layer_costs)
from repro.core.partition.profiles import MCU_EDGE, PAPER_PROFILE, PI_EDGE
from repro.core.partition.splitter import sweep_splits
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.models.cnn import (cnn_apply, init_cnn_params, prunable_layers,
                              tiny_cnn_config)
from repro.roofline.analysis import (check_quant_edge_roofline,
                                     quant_edge_roofline)

pytestmark = pytest.mark.quant

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "quant_edge_golden.json")

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis not installed (pip install -r requirements-dev.txt)")


@pytest.fixture(scope="module")
def qsetup():
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    masks = cnn_masks_from_ratios(
        params, cfg, {i: 0.5 for i in prunable_layers(cfg)})
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3)),
                   np.float32)
    return cfg, params, masks, x


# ---------------------------------------------------------------------------
# QuantPolicy: validation, serialization, digest fold
# ---------------------------------------------------------------------------
def test_quant_policy_validation_and_roundtrip():
    for bad in (dict(weight_bits=3), dict(backend="cuda"),
                dict(calibration="kl")):
        with pytest.raises(ValueError):
            QuantPolicy(**bad)
    for pol in (QuantPolicy(), QuantPolicy(weight_bits=4, per_channel=False),
                QuantPolicy(weight_bits=None, backend="ref")):
        assert QuantPolicy.from_json(pol.to_json()) == pol
    assert QuantPolicy().describe() == "int8/pc@auto"
    assert QuantPolicy(weight_bits=None, backend="ref").describe() == \
        "fp32@ref"


def test_resolve_backend_explicit():
    assert resolve_backend(QuantPolicy(backend="ref")) == ("ref", False)
    kind, interp = resolve_backend(QuantPolicy(backend="pallas"))
    assert kind == "pallas"
    if jax.default_backend() == "cpu":
        assert interp                 # no Mosaic CPU lowering: interpret


def test_plan_digest_folds_quant_only_when_set(qsetup):
    cfg, params, masks, _ = qsetup
    base = serving.DeploymentPlan.from_args(params, cfg, 6, masks=masks,
                                            compact=True)
    assert "quant" not in base.contract()            # fold-only-when-set
    q8 = serving.DeploymentPlan.from_args(
        params, cfg, 6, masks=masks, compact=True, quant=QuantPolicy())
    q4 = serving.DeploymentPlan.from_args(
        params, cfg, 6, masks=masks, compact=True,
        quant=QuantPolicy(weight_bits=4))
    assert base.digest != q8.digest != q4.digest
    # the backend is an execution detail, not part of the numerics
    # contract dimensioned keys pin — but it IS serialized, so two peers
    # still agree on it; only weight_bits/per_channel change numerics.
    assert q8.contract()["quant"]["weight_bits"] == 8


# ---------------------------------------------------------------------------
# weight quantization: the codec's bound, per channel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("per_channel", [True, False])
def test_weight_quant_error_within_half_scale(bits, per_channel):
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (5, 5, 6, 12)),
                   np.float32) * np.linspace(0.1, 3.0, 12)   # ragged ranges
    codes, scale, zero = quantize_weights(w, bits, per_channel)
    assert codes.dtype == np.uint8
    assert codes.max() <= BITS_LEVELS[bits]
    deq = codes.astype(np.float32) * scale + zero
    err = np.abs(deq - w)
    bound = np.broadcast_to(np.asarray(scale) * 0.5 + 1e-7, err.shape)
    assert (err <= bound).all()
    if per_channel:
        assert scale.shape == (12,)        # one (scale, zero) per channel


def test_per_channel_beats_per_tensor_on_ragged_ranges():
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (64, 16)),
                   np.float32) * np.r_[np.full(8, 0.05), np.full(8, 5.0)]
    for bits in (8, 4):
        pc = dequantize_weights({"wq": jnp.asarray(quantize_weights(
            w, bits, True)[0]), "scale": jnp.asarray(quantize_weights(
                w, bits, True)[1]), "zero": jnp.asarray(quantize_weights(
                    w, bits, True)[2])})
        q, s, z = quantize_weights(w, bits, False)
        pt = q.astype(np.float32) * s + z
        # the small-range channels are where per-channel wins
        small = np.abs(np.asarray(pc)[:, :8] - w[:, :8]).max()
        assert small < np.abs(pt[:, :8] - w[:, :8]).max()


#: (kernel, stride, padding, Cin) of every distinct conv geometry of the
#: benchmark's alexnet224 and vgg16pv configurations
CONV_GEOMETRIES = [(11, 4, 2, 3), (5, 1, 2, 64), (3, 1, 1, 3), (3, 1, 1, 64),
                   (3, 1, 1, 192)]


@pytest.mark.parametrize("kernel,stride,padding,cin", CONV_GEOMETRIES)
def test_conv_weight_gemm_layout_matches_patch_order(kernel, stride, padding,
                                                     cin):
    """``im2col``'s patch matrix, K-padded to the compiled kernel's block,
    against the GEMM-layout conv weights reproduces the conv to float
    tolerance; the pad columns are exact zeros, and the patch columns are
    ``conv_general_dilated_patches``' values bit for bit, in the row order
    ``conv_weight_gemm_layout`` gives the weights."""
    k = cin * kernel * kernel
    hw = 3 * stride + kernel
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                     (kernel, kernel, cin, 9)),
                   np.float32) / np.sqrt(k)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, hw, hw, cin))
    cols = np.asarray(im2col(x, kernel, stride, padding, KERNEL_BLOCK_K))
    cols = cols.reshape(-1, cols.shape[-1])
    assert cols.shape[1] == k or cols.shape[1] % KERNEL_BLOCK_K == 0
    assert not np.any(cols[:, k:])
    got = cols[:, :k] @ conv_weight_gemm_layout(w)
    want = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(got, np.asarray(want).reshape(-1, 9),
                               rtol=1e-4, atol=1e-5)
    # column r of the builder is the library's channel-major column order[r]
    channel_major = np.arange(k).reshape(cin, kernel, kernel)
    order = conv_weight_gemm_layout(
        channel_major.transpose(1, 2, 0)[..., None])[:, 0].astype(int)
    patches = jax.lax.conv_general_dilated_patches(
        x, (kernel, kernel), (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert np.array_equal(cols[:, :k],
                          np.asarray(patches).reshape(-1, k)[:, order])


@pytest.mark.parametrize("kernel,stride,padding,cin", CONV_GEOMETRIES)
def test_two_stage_patches_equal_one_stage_slices(kernel, stride, padding,
                                                  cin, monkeypatch):
    """Either builder ``im2col`` may take writes the same patch matrix,
    bit for bit, on a non-square input."""
    x = jax.random.normal(jax.random.PRNGKey(7),
                          (2, 3 * stride + kernel + 1, 3 * stride + kernel,
                           cin))
    built = []
    for rule in (1, 1 << 30):          # every kernel two-stage / none
        monkeypatch.setattr(quant, "TWO_STAGE_MIN_KERNEL", rule)
        built.append(np.asarray(im2col(x, kernel, stride, padding,
                                       KERNEL_BLOCK_K)))
    assert np.array_equal(*built)


@pytest.mark.parametrize("kernel,stride,padding,cin", CONV_GEOMETRIES)
def test_conv_in_compiled_kernel_layout_matches_ref(kernel, stride, padding,
                                                    cin, monkeypatch):
    """The layout the chip runs: ``quant_cnn_apply`` at the compiled
    kernel's K block pads the patches' K with zero columns and the weight
    rows to match, and the kernel (interpret mode, ``KERNEL_BLOCK_K``-wide
    K blocks) then agrees with the ``ref`` GEMM on the unpadded patches."""
    cfg = CNNConfig(name="one_conv", num_classes=2,
                    input_hw=(3 * stride + kernel,) * 2, input_channels=cin,
                    layers=(ConvLayerSpec("conv", out_channels=9,
                                          kernel=kernel, stride=stride,
                                          padding=padding),))
    params = init_cnn_params(jax.random.PRNGKey(8), cfg)
    qp = quantize_params(params, cfg, QuantPolicy(weight_bits=8))
    x = jax.random.normal(jax.random.PRNGKey(9),
                          (1, *cfg.input_hw, cin))
    want = quant_cnn_apply(qp, cfg, x, backend="ref")
    monkeypatch.setattr(quant, "gemm_block_k", lambda *_: KERNEL_BLOCK_K)
    patches = im2col(x, kernel, stride, padding,
                     quant.gemm_block_k("pallas", True))
    assert (patches.shape[-1] == cin * kernel ** 2
            or patches.shape[-1] % KERNEL_BLOCK_K == 0)
    got = quant_cnn_apply(qp, cfg, x, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _conv(n, kernel, stride, padding):
    return ConvLayerSpec("conv", out_channels=n, kernel=kernel,
                         stride=stride, padding=padding)


#: the benchmark's AlexNet-224 conv stack (11x11/s4, 5x5, three 3x3) at
#: small widths on a 67-px image; the edge ends after pool5 (layer 13)
ALEXNET_PATTERN = CNNConfig(
    name="alexnet_pattern", num_classes=4, input_hw=(67, 67),
    input_channels=3,
    layers=(_conv(8, 11, 4, 2), ConvLayerSpec("relu"),
            ConvLayerSpec("maxpool", kernel=3, stride=2),
            _conv(16, 5, 1, 2), ConvLayerSpec("relu"),
            ConvLayerSpec("maxpool", kernel=3, stride=2),
            _conv(16, 3, 1, 1), ConvLayerSpec("relu"),
            _conv(16, 3, 1, 1), ConvLayerSpec("relu"),
            _conv(16, 3, 1, 1), ConvLayerSpec("relu"),
            ConvLayerSpec("maxpool", kernel=3, stride=2),
            ConvLayerSpec("flatten"), ConvLayerSpec("dense", features=4)))


def test_alexnet_pattern_edge_takes_each_builder_by_kernel_size():
    """Lowered for the TPU (exported; no chip needed), the AlexNet-pattern
    edge calls ``masked_matmul`` once per conv and holds no convolution;
    its 11x11 and 5x5 convs take the two-stage builder, its three 3x3
    convs the one-stage one, and the program's scopes say so."""
    from collections import Counter
    from jax import export
    from repro.core.collab.runtime import split_step
    cfg, split = ALEXNET_PATTERN, 13
    qp = quantize_params(init_cnn_params(jax.random.PRNGKey(3), cfg), cfg,
                         QuantPolicy(weight_bits=8))
    qp = {k: v for k, v in qp.items() if int(k[1:]) < split}
    step = split_step(cfg, split, "edge", None, ("pallas", False))
    text = export.export(jax.jit(step), platforms=("tpu",))(
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), qp),
        jax.ShapeDtypeStruct((1, 67, 67, 3), jnp.float32)).mlir_module()
    convs = [s for s in cfg.layers[:split] if s.kind == "conv"]
    assert Counter(quant.im2col_builder(s.kernel) for s in convs) == {
        "im2col_two_stage": 2, "im2col_slices": 3}
    assert "/im2col_two_stage/" in text and "/im2col_slices/" in text
    assert len(re.findall(r"call @masked_matmul", text)) == len(convs)
    assert "stablehlo.convolution" not in text


# ---------------------------------------------------------------------------
# the differential campaign: every candidate split
# ---------------------------------------------------------------------------
def test_pallas_bit_identical_to_ref_at_every_split(qsetup):
    """weight_bits=None + whole-array interpret blocks: the Pallas
    kernel's edge prefix is BIT-identical to the pure-XLA ref at every
    candidate split boundary 0..N."""
    cfg, params, masks, x = qsetup
    qp = quantize_params(params, cfg, QuantPolicy(weight_bits=None))
    for split in range(len(cfg.layers) + 1):
        ref = quant_cnn_apply(qp, cfg, x, masks=masks, stop_layer=split,
                              backend="ref")
        pal = quant_cnn_apply(qp, cfg, x, masks=masks, stop_layer=split,
                              backend="pallas", interpret=True)
        assert np.array_equal(np.asarray(ref), np.asarray(pal)), \
            f"pallas/ref diverge at split {split}"


@pytest.mark.parametrize("bits", [8, 4])
def test_int8_layer_error_bounded_at_every_gemm(qsetup, bits):
    """Per conv/dense layer, |quantized - fp32| <= gemm_error_bound of
    that layer's true input (the provable affine contract)."""
    cfg, params, masks, x = qsetup
    fp = quantize_params(params, cfg, QuantPolicy(weight_bits=None))
    qp = quantize_params(params, cfg, QuantPolicy(weight_bits=bits))
    cur = jnp.asarray(x)
    for i, spec in enumerate(cfg.layers):
        nxt = quant_cnn_apply(fp, cfg, cur, masks=masks, start_layer=i,
                              stop_layer=i + 1)
        if spec.kind in ("conv", "dense"):
            got = quant_cnn_apply(qp, cfg, cur, masks=masks, start_layer=i,
                                  stop_layer=i + 1)
            if spec.kind == "conv":
                gin = jax.lax.conv_general_dilated_patches(
                    cur, (spec.kernel, spec.kernel),
                    (spec.stride, spec.stride),
                    [(spec.padding, spec.padding)] * 2,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
            else:
                gin = cur
            bound = gemm_error_bound(gin, qp[f"l{i}"]["scale"])
            err = jnp.abs(got - nxt)
            slack = 1e-5 + 1e-6 * jnp.abs(nxt)   # fp32 accumulation eps
            assert bool(jnp.all(err <= bound + slack)), \
                f"layer {i} ({spec.kind}): bound violated"
        cur = nxt


def test_int8_logits_close_to_fp32_end_to_end(qsetup):
    cfg, params, masks, x = qsetup
    dense = np.asarray(cnn_apply(params, cfg, x, masks=masks))
    qp = quantize_params(params, cfg, QuantPolicy(weight_bits=8))
    q = np.asarray(quant_cnn_apply(qp, cfg, x, masks=masks))
    assert np.abs(q - dense).max() < 0.5      # tiny net, random init
    # and the kernel path itself (fp32 weights) matches dense closely
    fp = quantize_params(params, cfg, QuantPolicy(weight_bits=None))
    k = np.asarray(quant_cnn_apply(fp, cfg, x, masks=masks))
    np.testing.assert_allclose(k, dense, rtol=1e-4, atol=1e-4)


def test_golden_numerics_regression(qsetup):
    """The quantized forward's logits on a pinned seed/input, against
    the tracked golden file — catches silent numerics drift (layout,
    codec, epilogue-order changes) between commits."""
    cfg, params, masks, x = qsetup
    qp = quantize_params(params, cfg, QuantPolicy(weight_bits=8))
    got = np.asarray(quant_cnn_apply(qp, cfg, x, masks=masks),
                     np.float32)
    with open(GOLDEN) as f:
        doc = json.load(f)
    want = np.asarray(doc["int8_ref_logits"], np.float32)
    assert got.shape == tuple(doc["shape"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# one contract, three backends
# ---------------------------------------------------------------------------
def test_quant_plan_serves_identically_on_all_backends(qsetup):
    cfg, params, masks, x2 = qsetup
    x = x2[:1]                                 # streaming is batch-1
    plan = serving.DeploymentPlan.from_args(
        params, cfg, 6, masks=masks, compact=True, port=29621,
        shape_link=False, quant=QuantPolicy(weight_bits=8, backend="ref"))
    local = serving.connect(plan, backend="local").infer(x)
    stream = serving.connect(plan, backend="streaming",
                             realtime_channel=False).infer(x)
    np.testing.assert_array_equal(stream["logits"], local["logits"])
    with serving.CloudServer(plan):
        with serving.connect(plan, backend="socket") as sess:
            sock = sess.infer(x)
    np.testing.assert_array_equal(sock["logits"], local["logits"])
    # the quantized edge stays close to the dense logits
    dense = np.asarray(cnn_apply(params, cfg, x, masks=masks))
    assert np.abs(local["logits"] - dense).max() < 0.5


def test_quant_plan_save_load_roundtrip(qsetup, tmp_path):
    cfg, params, masks, x = qsetup
    plan = serving.DeploymentPlan.from_args(
        params, cfg, 6, masks=masks, compact=True,
        quant=QuantPolicy(weight_bits=8, backend="ref"))
    before = serving.connect(plan, backend="local").infer(x)
    loaded = serving.DeploymentPlan.load(plan.save(str(tmp_path / "q")))
    assert loaded.quant == plan.quant
    assert loaded.digest == plan.digest
    assert "quant" in loaded.describe()
    after = serving.connect(loaded, backend="local").infer(x)
    np.testing.assert_array_equal(after["logits"], before["logits"])


def test_unquantized_kernel_plan_matches_dense_plan_logits(qsetup):
    """weight_bits=None kernel dispatch through a real session: the ref
    and pallas backends agree bit-for-bit with each other (the dispatch
    contract), and with the dense plan to float tolerance (im2col
    reassociates the conv reduction, so exact equality is not owed)."""
    cfg, params, masks, x = qsetup
    kw = dict(masks=masks, compact=True)
    dense = serving.connect(serving.DeploymentPlan.from_args(
        params, cfg, 6, **kw), backend="local").infer(x)
    ref = serving.connect(serving.DeploymentPlan.from_args(
        params, cfg, 6, quant=QuantPolicy(weight_bits=None, backend="ref"),
        **kw), backend="local").infer(x)
    pal = serving.connect(serving.DeploymentPlan.from_args(
        params, cfg, 6,
        quant=QuantPolicy(weight_bits=None, backend="pallas"), **kw),
        backend="local").infer(x)
    np.testing.assert_array_equal(ref["logits"], pal["logits"])
    np.testing.assert_allclose(ref["logits"], dense["logits"],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# calibration hook + roofline check
# ---------------------------------------------------------------------------
def test_calibration_feeds_split_sweep(qsetup):
    cfg, params, masks, x = qsetup
    qp = quantize_params(params, cfg, QuantPolicy(weight_bits=8))
    cal = calibrate_quant_edge(qp, cfg, x[:1], masks=masks, repeats=2)
    assert isinstance(cal, KernelCalibration)
    assert len(cal.layer_s) == len(cfg.layers)
    assert all(t > 0 for t in cal.layer_s)
    assert cal.total_s(4) <= cal.total_s() + 1e-12
    rows = sweep_splits(quantized_cnn_layer_costs(cfg, masks, 8),
                        PAPER_PROFILE, cnn_input_bytes(cfg),
                        measured_device_s=cal.layer_s)
    assert len(rows) == len(cfg.layers) + 1
    best = min(rows, key=lambda r: r["T"])
    assert 0 <= best["split"] <= len(cfg.layers)


@pytest.mark.parametrize("profile", [MCU_EDGE, PI_EDGE],
                         ids=lambda p: p.name)
def test_quantized_fc_layers_reach_memory_bound_ceiling(qsetup, profile):
    """The headline roofline claim: int8 weight streaming puts the
    batch-1 fc GEMMs in the memory-bound regime on both edge profiles."""
    cfg, _, masks, _ = qsetup
    rows = check_quant_edge_roofline(cfg, masks, profile, weight_bits=8)
    fc = [r for r in rows if r["name"].startswith("fc")]
    assert fc and all(r["memory_bound"] for r in fc)
    assert all(r["memory_share"] >= 0.5 for r in fc)


def test_fp32_fc_layers_stay_compute_bound_on_mcu(qsetup):
    """The contrast that makes the int8 story meaningful: at fp32 the
    MCU's soft-float throughput keeps the same fc layers compute-bound."""
    cfg, _, masks, _ = qsetup
    rows = quant_edge_roofline(cfg, masks, MCU_EDGE, weight_bits=None)
    fc = [r for r in rows if r["name"].startswith("fc")]
    assert fc and not any(r["memory_bound"] for r in fc)
    with pytest.raises(AssertionError, match="compute-bound"):
        check_quant_edge_roofline(cfg, masks, MCU_EDGE, weight_bits=None)


# ---------------------------------------------------------------------------
# hypothesis property tests (skip cleanly when not installed)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    SET = settings(max_examples=25, deadline=None)

    @needs_hypothesis
    @SET
    @given(st.integers(1, 24), st.integers(1, 48), st.integers(1, 32),
           st.integers(0, 2 ** 31 - 1))
    def test_prop_pallas_whole_block_bit_identical(m, k, n, seed):
        from repro.core.collab.quant import _gemm
        ka, kb, km = jax.random.split(jax.random.PRNGKey(seed), 3)
        a = jax.random.normal(ka, (m, k), jnp.float32)
        b = jax.random.normal(kb, (k, n), jnp.float32)
        mask = (jax.random.uniform(km, (n,)) > 0.5).astype(jnp.float32)
        got = _gemm(a, b, mask, "pallas", True)
        want = _gemm(a, b, mask, "ref", False)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @needs_hypothesis
    @SET
    @given(st.integers(1, 40), st.integers(1, 24),
           st.sampled_from([8, 4]), st.integers(0, 2 ** 31 - 1))
    def test_prop_gemm_error_bound_holds(k, n, bits, seed):
        kw, kx = jax.random.split(jax.random.PRNGKey(seed))
        w = np.asarray(jax.random.normal(kw, (k, n)), np.float32) * 3.0
        x = jax.random.normal(kx, (2, k), jnp.float32)
        codes, scale, zero = quantize_weights(w, bits, True)
        deq = codes.astype(np.float32) * scale + zero
        err = jnp.abs(x @ jnp.asarray(deq) - x @ jnp.asarray(w))
        bound = gemm_error_bound(x, scale)
        assert bool(jnp.all(err <= bound + 1e-4))

    @needs_hypothesis
    @SET
    @given(st.integers(2, 200), st.sampled_from([255, 15]),
           st.integers(0, 2 ** 31 - 1))
    def test_prop_affine_quantize_half_scale(size, levels, seed):
        rng = np.random.RandomState(seed % (2 ** 32 - 1))
        x = (rng.randn(size) * rng.uniform(0.01, 10)).astype(np.float32)
        q, scale, zero = affine_quantize(x, levels)
        assert q.dtype == np.uint8 and q.max() <= levels
        deq = q.astype(np.float32) * scale + zero
        assert np.abs(deq - x).max() <= scale * 0.5 + 1e-6
