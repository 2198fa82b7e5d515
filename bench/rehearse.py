"""Compile a configuration's served path for a described TPU v5e, with no
chip attached, so that what the chip's compiler refuses fails here.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py [--config vgg16]

Run by hand before chip time; it is not a test. It compiles, for one chip
of a described ``v5e:2x2`` host:

* every edge GEMM shape of the configuration through ``masked_matmul``;
* the whole edge half at batch 1 over int8 weight codes, as the plan
  deploys it;
* the cloud half at the largest batch bucket, its params described by
  shape alone.

For each it prints ``memory_analysis()`` and the number of Pallas calls
(``tpu_custom_call``) in the executable.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="vgg16")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import costs, deploy
    from repro.core.collab.runtime import half_params, split_step
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.models.cnn import compact_cnn_config, init_cnn_params

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def st(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def report(what, compiled):
        mem = compiled.memory_analysis()
        print(f"{what}: {compiled.as_text().count('tpu_custom_call')} "
              f"Pallas calls; arguments {mem.argument_size_in_bytes} B, "
              f"output {mem.output_size_in_bytes} B, temp "
              f"{mem.temp_size_in_bytes} B, code "
              f"{mem.generated_code_size_in_bytes} B", flush=True)

    for m, k, n in costs.edge_gemms(cfg):
        report(f"masked_matmul M={m} K={k} N={n}", jax.jit(masked_matmul).lower(
            st((m, k)), st((k, n)), st((n,))).compile())

    split = cfg["deployment"]["split"]
    widths = costs.deployed_widths(cfg)
    keep = {i: jnp.arange(cfg["layers"][i]["out_channels"]) < w
            for i, w in widths.items()}
    ccfg = compact_cnn_config(deploy.cnn_config(cfg), keep)
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.PRNGKey(0),
                                                    ccfg))
    hw = cfg["input_hw"]

    qshapes = {}
    for i, _, m, k, n in costs.layer_gemms(cfg):
        if i < split:
            qshapes[f"l{i}"] = {"wq": st((k, n), jnp.uint8),
                                "scale": st((n,)), "zero": st((n,)),
                                "b": st((n,))}
    edge = split_step(ccfg, split, "edge", None, ("pallas", False))
    compiled = jax.jit(edge).lower(
        qshapes, st((1, hw, hw, cfg["input_channels"]))).compile()
    report(f"edge half c={split} batch 1", compiled)
    feat = compiled.out_info.shape
    print(f"split-boundary features {feat}", flush=True)

    bucket = cfg["deployment"]["max_batch"]
    cloud = split_step(ccfg, split, "cloud", batched=True)
    pshapes = jax.tree_util.tree_map(lambda a: st(a.shape, a.dtype),
                                     half_params(params, split, "cloud"))
    compiled = jax.jit(cloud).lower(
        pshapes, st((bucket,) + tuple(feat[1:]))).compile()
    report(f"cloud half c={split} bucket {bucket}", compiled)


if __name__ == "__main__":
    main()
