"""Operations and bytes of one served request, counted from a configuration's
layer list at its deployed widths (after pruning, before any padding).

These counts are the yardstick of ``mfu`` and ``masked_matmul_roofline``:
they follow from the shapes alone, so they stay the same whatever
implements a layer. A conv is counted as the im2col GEMM the edge runs:
M = output positions, K = input channels x kernel area, N = output
channels, 2*M*K*N operations. Pools, relus and flattens count no
operations. A dense layer is a GEMM with M = 1.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

F32 = 4
INT8 = 1


def _out(hw: int, k: int, s: int, p: int) -> int:
    return (hw + 2 * p - k) // s + 1


def deployed_widths(cfg: Dict) -> Dict[int, int]:
    """Output channels each conv keeps: the first ``prune_from_conv`` convs
    keep all of theirs, the others ``round(prune_keep * C)`` (at least 1)."""
    dep = cfg["deployment"]
    out, n_conv = {}, 0
    for i, spec in enumerate(cfg["layers"]):
        if spec["kind"] != "conv":
            continue
        c = spec["out_channels"]
        if n_conv >= dep["prune_from_conv"]:
            c = max(1, int(round(dep["prune_keep"] * c)))
        out[i] = c
        n_conv += 1
    return out


def layer_gemms(cfg: Dict) -> List[Tuple[int, str, int, int, int]]:
    """(layer index, kind, M, K, N) of every conv and dense layer for one
    image, at deployed widths."""
    widths = deployed_widths(cfg)
    hw, c, flat = cfg["input_hw"], cfg["input_channels"], None
    out = []
    for i, spec in enumerate(cfg["layers"]):
        kind = spec["kind"]
        if kind == "conv":
            k, s, p = spec["kernel"], spec["stride"], spec["padding"]
            hw = _out(hw, k, s, p)
            out.append((i, "conv", hw * hw, c * k * k, widths[i]))
            c = widths[i]
        elif kind == "maxpool":
            hw = _out(hw, spec["kernel"], spec["stride"], 0)
        elif kind == "flatten":
            flat = c * hw * hw
        elif kind == "dense":
            out.append((i, "dense", 1, flat, spec["features"]))
            flat = spec["features"]
    return out


def split_flops(cfg: Dict) -> Dict[str, float]:
    """Operations per request on each side of the deployed split."""
    split = cfg["deployment"]["split"]
    tot = {"edge": 0.0, "cloud": 0.0}
    for i, _, m, k, n in layer_gemms(cfg):
        tot["edge" if i < split else "cloud"] += 2.0 * m * k * n
    return tot


def request_flops(cfg: Dict) -> float:
    """Operations of one request, edge and cloud halves together."""
    return sum(split_flops(cfg).values())


def cloud_weight_bytes(cfg: Dict) -> int:
    """float32 weights and biases the cloud half reads for one row."""
    split = cfg["deployment"]["split"]
    return sum((k * n + n) * F32 for i, _, m, k, n in layer_gemms(cfg)
               if i >= split)


def edge_gemms(cfg: Dict) -> List[Tuple[int, int, int]]:
    """(M, K, N) of each masked_matmul call of one edge forward: every
    conv and dense layer before the split, in order."""
    split = cfg["deployment"]["split"]
    return [(m, k, n) for i, _, m, k, n in layer_gemms(cfg) if i < split]


def masked_matmul_bytes(m: int, k: int, n: int) -> int:
    """Bytes one masked GEMM must move at the dtypes the configuration
    states: float32 patches A (M x K), int8 weight codes B (K x N) with a
    float32 scale and zero per column, the float32 column mask, and the
    float32 output C (M x N)."""
    return m * k * F32 + k * n * INT8 + 2 * n * F32 + n * F32 + m * n * F32


def masked_matmul_floor_s(m: int, k: int, n: int, peak_flops: float,
                          peak_bytes_per_s: float) -> Tuple[float, str]:
    """The least time the chip could take for one masked GEMM, and which
    bound sets it (``"compute"`` or ``"memory"``)."""
    t_c = 2.0 * m * k * n / peak_flops
    t_m = masked_matmul_bytes(m, k, n) / peak_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
