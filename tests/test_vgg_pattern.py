"""VGG-16-D's layer pattern on the served socket path at a tiny size:
stages of stacked 3x3/s1/p1 convs and 2x2 pools, 13 int8 convs on the
edge, the split after pool5 (c = 31), a 38-way head. ``infer_many``'s
grouped frames answer bit-identically to one ``infer`` per request, and
the bank names its programs as the benchmark's device-time readers expect
(``jit_edge_c31``, ``jit_cloud_c31_rows``). The edge program, lowered for
the TPU, builds its im2col patches with no convolution."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from repro import serving
from repro.configs.base import CNNConfig, ConvLayerSpec
from repro.core.collab.quant import (KERNEL_BLOCK_K, im2col_builder,
                                     quantize_params)
from repro.core.collab.runtime import split_step
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.models.cnn import init_cnn_params

SPLIT = 31
L = ConvLayerSpec


def _stage(width, convs):
    layers = []
    for _ in range(convs):
        layers += [L("conv", out_channels=width, kernel=3, stride=1,
                     padding=1), L("relu")]
    return layers + [L("maxpool", kernel=2, stride=2)]


def vgg_pattern_config() -> CNNConfig:
    return CNNConfig(
        name="tiny_vgg",
        layers=tuple(_stage(8, 2) + _stage(16, 2) + _stage(32, 3)
                     + _stage(64, 3) + _stage(64, 3)
                     + [L("flatten"), L("dense", features=64), L("relu"),
                        L("dense", features=64), L("relu"),
                        L("dense", features=38)]),
        num_classes=38, input_hw=(32, 32))


def he_normal_params(cfg: CNNConfig, seed: int):
    """He-normal weights and small biases in the shapes ``init_cnn_params``
    gives, drawn with numpy: no per-shape compile of the random draws."""
    rng = np.random.default_rng(seed)

    def draw(a):
        fan_in = int(np.prod(a.shape[:-1])) if a.ndim > 1 else 1
        scale = np.sqrt(2.0 / fan_in) if a.ndim > 1 else 0.05
        return (rng.standard_normal(a.shape) * scale).astype(np.float32)
    shapes = jax.eval_shape(lambda k: init_cnn_params(k, cfg),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(draw, shapes)


@pytest.fixture(scope="module")
def setup():
    cfg = vgg_pattern_config()
    params = he_normal_params(cfg, 3)
    convs = [i for i, s in enumerate(cfg.layers) if s.kind == "conv"]
    masks = cnn_masks_from_ratios(params, cfg, {i: 0.5 for i in convs[1:]})
    rng = np.random.RandomState(3)
    imgs = [rng.rand(1, 32, 32, 3).astype(np.float32) for _ in range(9)]
    return cfg, params, masks, imgs


def _plan(setup, port):
    cfg, params, masks, _ = setup
    return serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        shape_link=False, port=port,
        quant=serving.QuantPolicy(weight_bits=8),
        batching=serving.BatchingPolicy(max_batch=4, max_wait_ms=2.0))


def test_grouped_frames_bit_identical_to_single_requests(setup):
    imgs = setup[3]
    plan = _plan(setup, 29872)
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            singles = [s.infer(im) for im in imgs]
            grouped = s.infer_many(imgs)
        lane = next(iter(srv.lane_stats().values()))
        srv.stop()
    assert lane["rows"] == 2 * len(imgs)
    assert lane["frames"] == len(imgs) + -(-len(imgs) // 4)
    for a, b in zip(singles, grouped):
        assert a["logits"].shape == (1, 38)
        assert np.array_equal(a["logits"], b["logits"])


def test_bank_names_its_programs_by_side_and_split(setup):
    """The module names the device trace shows: the edge half at c = 31,
    and the row-mapped batched cloud half."""
    imgs = setup[3]
    plan = _plan(setup, 29873)
    with serving.CloudServer(plan, max_clients=None) as srv:
        with serving.connect(plan, backend="socket") as s:
            edge_fn = s._client._bank.get(SPLIT)[0]
            x = np.asarray(imgs[0])
            edge_text = edge_fn.func.lower(*edge_fn.args, x).as_text()
            feat = np.asarray(edge_fn(x))
        cloud_fn = srv._engine.bank.get(SPLIT, batch_bucket=4)[1]
        srv.stop()
    cloud_text = cloud_fn.func.lower(
        *cloud_fn.args, np.repeat(feat, 4, axis=0)).as_text()
    assert "module @jit_edge_c31 " in edge_text
    assert "module @jit_cloud_c31_rows " in cloud_text
    assert feat.shape == (1, 1, 1, 32)


def test_edge_program_for_tpu_builds_patches_without_convolution(setup):
    """Lowered for the TPU (exported; no chip or TPU compiler needed), the
    compiled-kernel edge step calls ``masked_matmul`` once per conv, each
    call's patch operand already K-padded to the kernel's block, and holds
    no convolution: the patches are slices, not a grouped identity conv,
    and every 3x3 conv takes the one-stage builder."""
    cfg, params, _, _ = setup
    qp = quantize_params(params, cfg, serving.QuantPolicy(weight_bits=8))
    qp = {k: v for k, v in qp.items() if int(k[1:]) < SPLIT}
    step = split_step(cfg, SPLIT, "edge", None, ("pallas", False))
    exp = export.export(jax.jit(step), platforms=("tpu",))(
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), qp),
        jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32))
    text = exp.mlir_module()
    n_conv = sum(s.kind == "conv" for s in cfg.layers[:SPLIT])
    patch_k = [int(k) for k in re.findall(
        r"call @masked_matmul\w* ?\(.*?\) : \(tensor<(?:\d+x)+?(\d+)xf32>",
        text)]
    assert len(patch_k) == n_conv == 13
    assert "tpu_custom_call" in text
    assert "stablehlo.convolution" not in text
    assert all(k <= KERNEL_BLOCK_K or k % KERNEL_BLOCK_K == 0
               for k in patch_k), patch_k
    convs = [s for s in cfg.layers[:SPLIT] if s.kind == "conv"]
    assert {im2col_builder(s.kernel) for s in convs} == {"im2col_slices"}
    assert "/im2col_slices/" in text and "im2col_two_stage" not in text
