"""The system under test, built from a configuration file: the program's
``CNNConfig`` and ``DeploymentPlan`` for the weights and masks the benchmark
made. This is the one module of the benchmark, besides the hand-run
``rehearse.py`` and ``sweep.py``, that imports the program.
"""
from __future__ import annotations

from typing import Dict, Optional


def cnn_config(cfg: Dict):
    """The program's ``CNNConfig`` for a configuration file's layer list."""
    from repro.configs.base import CNNConfig, ConvLayerSpec
    layers = tuple(ConvLayerSpec(**spec) for spec in cfg["layers"])
    return CNNConfig(name=cfg["name"], layers=layers,
                     num_classes=cfg["num_classes"],
                     input_hw=(cfg["input_hw"], cfg["input_hw"]),
                     input_channels=cfg["input_channels"])


def build_plan(cfg: Dict, params, masks, port: int,
               weight_bits: Optional[int] = None):
    """A ``DeploymentPlan`` of the configuration's deployment section over
    the given float32 weights and channel masks. ``weight_bits`` overrides
    the edge's weight width (the control runs the program's int4 path)."""
    from repro import serving
    dep = cfg["deployment"]
    bits = dep["weight_bits"] if weight_bits is None else weight_bits
    return serving.DeploymentPlan.from_args(
        params, cnn_config(cfg), dep["split"], masks=masks, compact=True,
        codec=dep["codec"], port=port, shape_link=dep["shape_link"],
        quant=serving.QuantPolicy(weight_bits=bits),
        batching=serving.BatchingPolicy(max_batch=dep["max_batch"],
                                        max_wait_ms=dep["max_wait_ms"]))


def edge_backend(plan) -> tuple:
    """How the plan's edge GEMM resolves here: ``("pallas", False)`` is the
    compiled kernel."""
    from repro.core.collab.quant import resolve_backend
    return resolve_backend(plan.quant)


def find_batcher():
    """The live ``DynamicBatcher`` of the one running ``CloudServer``.

    The server keeps its batcher inside its serve loop and fills
    ``batch_stats`` only on shutdown, so the benchmark reads the counters
    at the window's edges from the object itself."""
    import gc

    from repro.core.collab.batching import DynamicBatcher
    live = [o for o in gc.get_objects()
            if isinstance(o, DynamicBatcher) and not o._stop.is_set()]
    if len(live) != 1:
        raise RuntimeError(f"expected one running batcher, found {len(live)}")
    return live[0]


def lane_totals(batcher) -> Dict[str, float]:
    """``LaneStats`` counters summed over the batcher's lanes."""
    keys = ("rows", "frames", "batches", "padded_rows", "busy_s",
            "failed_rows")
    tot = {k: 0 for k in keys}
    for rec in batcher.stats().values():
        for k in keys:
            tot[k] += rec[k]
    return tot
