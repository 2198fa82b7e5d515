"""Analytic roofline of the quantized edge kernel on an edge
``ComputeProfile``: per-layer compute vs weight-streaming time, and the
check that the quantized GEMM layers sit at the memory-bound ceiling.
"""
from __future__ import annotations

from typing import Optional


def quant_edge_roofline(cfg, masks, profile,
                        weight_bits: Optional[int] = 8) -> list:
    """Per-layer roofline of the quantized kernel edge path on an edge
    ``ComputeProfile``: compute at the profile's int8 MAC throughput
    (fp32 throughput when ``weight_bits=None``), memory as weight
    streaming at the quantized width *plus* the activation traffic the
    split model already prices (``2 * out_bytes``). The interesting
    layers are the batch-1 GEMMs (``fc*``): their weight traffic is
    O(model) while their compute is only 2 FLOPs per weight, so int8
    pushes them through the ridge point into the memory-bound regime —
    which is the whole point of weight-only quantization on an edge
    device, and what ``check_quant_edge_roofline`` pins for the MCU/Pi
    profiles.

    Returns one dict per conv/dense layer: ``{index, name,
    t_compute_s, t_memory_s, memory_bound, memory_share}`` with
    ``memory_share = t_memory / (t_compute + t_memory)`` (how close the
    kernel's modeled time sits to the pure memory-streaming ceiling)."""
    from repro.core.partition.latency_model import quantized_cnn_layer_costs
    ops_per_s = (profile.flops_per_s if weight_bits is None
                 else profile.int8_ops_per_s)
    rows = []
    for c in quantized_cnn_layer_costs(cfg, masks, weight_bits):
        if not (c.name.startswith("conv") or c.name.startswith("fc")):
            continue
        t_c = c.flops / ops_per_s
        t_m = (c.params_bytes + 2 * c.out_bytes) / profile.mem_bw
        rows.append({"index": c.index, "name": c.name,
                     "t_compute_s": t_c, "t_memory_s": t_m,
                     "memory_bound": t_m >= t_c,
                     "memory_share": t_m / (t_c + t_m) if t_c + t_m else 1.0})
    return rows


def check_quant_edge_roofline(cfg, masks, profile,
                              weight_bits: Optional[int] = 8,
                              min_memory_share: float = 0.5) -> list:
    """Assert the quantized GEMM (``fc``) layers approach the
    memory-bound ceiling on ``profile``: every one must be
    memory-bound (``t_memory >= t_compute``) with a memory share of at
    least ``min_memory_share`` — i.e. the kernel's modeled time is
    dominated by weight streaming, so the analytic split model prices
    the quantized edge at (close to) its bandwidth floor. Raises
    ``AssertionError`` naming the offending layer; returns the full
    ``quant_edge_roofline`` report on success."""
    rows = quant_edge_roofline(cfg, masks, profile, weight_bits)
    for r in rows:
        if not r["name"].startswith("fc"):
            continue
        assert r["memory_bound"], (
            f"{r['name']} on {profile.name}: compute-bound "
            f"(t_compute={r['t_compute_s']:.3e}s > "
            f"t_memory={r['t_memory_s']:.3e}s) at weight_bits="
            f"{weight_bits} — the quantized kernel does not reach the "
            f"memory-bound ceiling")
        assert r["memory_share"] >= min_memory_share, (
            f"{r['name']} on {profile.name}: memory share "
            f"{r['memory_share']:.2f} < {min_memory_share} at "
            f"weight_bits={weight_bits}")
    return rows
