"""Chip compile rehearsals: the served AlexNet-224 path compiled for a
described (not attached) TPU v5e, so what the chip's compiler refuses
fails here, on the host.

* ``masked_matmul`` compiled (not interpreted) at the edge GEMM shapes of
  AlexNet-224, dense and pruned to half the channels;
* the cloud half after pool5 (c = 13) at batch bucket 8, with its params
  passed as arguments described by shape alone, fitting one chip's HBM.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU compiler's library at a time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.alexnet_plantvillage import CONFIG
from repro.core.collab.runtime import half_params, split_step
from repro.kernels.masked_matmul.ops import masked_matmul
from repro.models.cnn import compact_cnn_config, init_cnn_params, layer_shapes

V5E_HBM_BYTES = 16 * 2**30

#: (M, K, N) of the edge GEMMs: im2col rows x patch features x channels
EDGE_GEMMS = {
    "conv1": (3025, 363, 64),
    "conv2": (729, 1600, 192),
    "conv3": (169, 1728, 384),
    "conv3_half": (169, 864, 192),
    "conv4": (169, 3456, 256),
    "conv5": (169, 2304, 256),
    "fc6_b1": (1, 9216, 4096),
    "fc6_half_b8": (8, 4608, 4096),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile that lands in the persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name", sorted(EDGE_GEMMS))
def test_masked_matmul_compiles_for_v5e(one_chip, name):
    M, K, N = EDGE_GEMMS[name]
    compiled = jax.jit(masked_matmul).lower(
        _struct((M, K), jnp.float32, one_chip),
        _struct((K, N), jnp.float32, one_chip),
        _struct((N,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_alexnet_cloud_step_at_bucket_8_fits_one_v5e(one_chip):
    split, bucket = 13, 8
    half = {i: np.arange(s.out_channels) < s.out_channels // 2
            for i, s in enumerate(CONFIG.layers)
            if s.kind == "conv" and i > 0}
    cfg = compact_cnn_config(CONFIG, half)
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.PRNGKey(0),
                                                    cfg))
    pshapes = jax.tree_util.tree_map(
        lambda a: _struct(a.shape, a.dtype, one_chip),
        half_params(params, split, "cloud"))
    c, h, w = layer_shapes(cfg)[split - 1]               # pool5 output
    assert (h, w, c) == (6, 6, 128)
    step = split_step(cfg, split, "cloud", batched=True)
    compiled = jax.jit(step).lower(
        pshapes, _struct((bucket, h, w, c), jnp.float32, one_chip)).compile()
    assert compiled.out_info.shape == (bucket, CONFIG.num_classes)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES
    # the weights arrive as arguments, not as constants baked into the
    # executable: fc6..fc8 alone are ~140 MB of float32
    assert mem.argument_size_in_bytes > 100 * 2**20
    assert mem.generated_code_size_in_bytes < 32 * 2**20
