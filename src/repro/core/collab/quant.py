"""Quantized kernel edge path — the layer that makes the pruning payoff
*physical* (ROADMAP item 3).

The paper's edge submodel historically ran fp32 dense ``jnp`` even after
compaction, so every latency/energy number downstream of ``sweep_splits``
was modeled, never measured. This module wires the edge forward through
the ``kernels/masked_matmul`` column-masked GEMM instead:

  * **conv layers** lower to im2col (``im2col``: the spatially padded
    activation's strided slices, concatenated tap-major into the patch
    matrix, large kernels in two stages, K zero-padded to the compiled
    kernel's block)
    followed by one masked GEMM against the HWIO weights re-laid-out as
    ``(kh*kw*Cin, Cout)``;
  * **dense layers** are the masked GEMM directly;
  * relu / maxpool / flatten keep the exact ``models.cnn.cnn_apply``
    ops, so those layers stay bit-identical to the dense reference.

Weights are optionally quantized to int8/int4 **per output channel**
with the wire codec's proven affine math
(``protocol.affine_quantize`` — the same min/max, rint, clip formula
every int8 feature frame already round-trips through), giving the
provable per-layer contract

    |dequant(w) - w| <= scale_n / 2        (per output channel n)

and therefore, for a GEMM row ``x``,

    |y_quant - y_fp32|_n <= (scale_n / 2) * ||x||_1

(``gemm_error_bound``). ``weight_bits=None`` keeps fp32 weights and
changes only the dispatch — the differential suite pins that
configuration bit-identical between the Pallas kernel (interpret mode,
whole-array blocks) and its pure-XLA ``ref`` twin.

Backend resolution (``resolve_backend``):

  * ``"ref"``    — pure-XLA im2col + ``masked_matmul_ref`` (the fast CPU
    path: XLA's native GEMM, used for wall-clock benchmarking on CI);
  * ``"pallas"`` — the real kernel body; interpret mode is forced on CPU
    (or under ``kernels.dispatch.use_pallas(interpret=True)``), compiled
    elsewhere;
  * ``"auto"``   — ``pallas`` when the global dispatch switch is on or a
    real accelerator backs JAX, else ``ref``.

``SplitFnBank`` consumes this module when a ``DeploymentPlan`` carries a
``quant`` section: the *edge* closures of every candidate split dispatch
through ``quant_cnn_apply`` while the cloud halves stay fp32 dense (the
server is not the device the paper quantizes for). See
``docs/quantized-edge.md``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CNNConfig
from repro.core.collab.protocol import affine_quantize
from repro.kernels import dispatch
from repro.kernels.masked_matmul.ops import masked_matmul
from repro.kernels.masked_matmul.ref import masked_matmul_ref

#: affine code-point count per bit width (the codec uses 255 for int8)
BITS_LEVELS: Dict[int, int] = {8: 255, 4: 15}
BACKENDS: Tuple[str, ...] = ("auto", "pallas", "ref")
CALIBRATIONS: Tuple[str, ...] = ("minmax",)

#: "whole-array" block request: ops.py clamps each block to the actual
#: dim, collapsing the grid to (1, 1, 1) — in interpret mode that makes
#: the kernel body ONE dot_general over the unpadded operands, which is
#: bit-identical to the XLA ref GEMM (the basis of the differential
#: suite's exactness contract). Compiled TPU runs keep the native 128s.
WHOLE_BLOCK = 1 << 30

#: the K block ``_gemm`` launches the compiled kernel with: each conv's
#: patch matrix and weight rows are padded to it (``gemm_block_k``)
KERNEL_BLOCK_K = 128

#: ``im2col`` builds a conv of this kernel size or larger in two stages
#: (``im2col_builder``)
TWO_STAGE_MIN_KERNEL = 5


@dataclass(frozen=True)
class QuantPolicy:
    """The ``quant`` section of a ``DeploymentPlan``: how the edge
    submodel's conv/dense layers execute.

    ``weight_bits`` — 8 or 4 for per-channel affine weight quantization,
    ``None`` for fp32 weights (kernel dispatch only — the bit-identity
    configuration). ``per_channel`` quantizes each output channel with
    its own (scale, zero); ``False`` uses one pair per tensor.
    ``backend`` picks the GEMM implementation (see module docstring);
    ``calibration`` names the range estimator (only ``"minmax"`` — the
    codec's — exists today). Folded into the plan digest **only when
    set**, like the other optional sections: both peers must agree on
    the edge's numerics for golden-logits comparisons to mean anything.
    """
    weight_bits: Optional[int] = 8
    per_channel: bool = True
    backend: str = "auto"
    calibration: str = "minmax"

    def __post_init__(self) -> None:
        if self.weight_bits is not None and self.weight_bits not in BITS_LEVELS:
            raise ValueError(f"weight_bits must be one of "
                             f"{sorted(BITS_LEVELS)} or None, "
                             f"got {self.weight_bits!r}")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r} "
                             f"(use {BACKENDS})")
        if self.calibration not in CALIBRATIONS:
            raise ValueError(f"unknown calibration {self.calibration!r} "
                             f"(use {CALIBRATIONS})")

    def to_json(self) -> Dict[str, Any]:
        """Serializable section dict (``weight_bits`` is the only
        dimensioned key; the rest are enums/flags)."""
        return {"weight_bits": self.weight_bits,
                "per_channel": self.per_channel,
                "backend": self.backend,
                "calibration": self.calibration}

    @classmethod
    def from_json(cls, doc: Dict[str, Any]) -> "QuantPolicy":
        """Inverse of ``to_json`` (absent keys take the defaults)."""
        return cls(weight_bits=doc.get("weight_bits"),
                   per_channel=bool(doc.get("per_channel", True)),
                   backend=doc.get("backend", "auto"),
                   calibration=doc.get("calibration", "minmax"))

    def describe(self) -> str:
        """Short human summary, e.g. ``int8/pc@auto`` or ``fp32@ref``."""
        w = ("fp32" if self.weight_bits is None
             else f"int{self.weight_bits}"
                  + ("/pc" if self.per_channel else "/pt"))
        return f"{w}@{self.backend}"


def resolve_backend(policy: QuantPolicy) -> Tuple[str, bool]:
    """-> (``"pallas"`` | ``"ref"``, interpret). Resolved once at bank
    build time; the Pallas kernel always interprets on CPU hosts (there
    is no Mosaic CPU lowering) and compiles on real accelerators."""
    on_cpu = jax.default_backend() == "cpu"
    if policy.backend == "ref":
        return "ref", False
    if policy.backend == "pallas" or dispatch.enabled():
        return "pallas", bool(dispatch.interpret() or on_cpu)
    return ("ref", False) if on_cpu else ("pallas", False)


# ---------------------------------------------------------------------------
# weight quantization (the codec's affine math, per output channel)
# ---------------------------------------------------------------------------
def quantize_weights(w: np.ndarray, bits: int, per_channel: bool = True
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Quantize a weight tensor's values onto ``BITS_LEVELS[bits]`` code
    points with ``protocol.affine_quantize`` — per slice of the LAST
    axis (the output channel) when ``per_channel``. Returns
    (uint8 codes in ``w``'s shape, scale, zero); scale/zero are float32
    arrays of shape ``(N,)`` (or scalars for per-tensor)."""
    levels = BITS_LEVELS[bits]
    w = np.asarray(w, np.float32)
    if not per_channel:
        q, s, z = affine_quantize(w, levels)
        return q, np.float32(s), np.float32(z)
    flat = w.reshape(-1, w.shape[-1])
    codes = np.empty(flat.shape, np.uint8)
    scale = np.empty(flat.shape[-1], np.float32)
    zero = np.empty(flat.shape[-1], np.float32)
    for n in range(flat.shape[-1]):
        codes[:, n], scale[n], zero[n] = affine_quantize(flat[:, n], levels)
    return codes.reshape(w.shape), scale, zero


def conv_weight_gemm_layout(w: np.ndarray) -> np.ndarray:
    """HWIO conv weights ``(kh, kw, Cin, N)`` -> the im2col GEMM operand
    ``(kh*kw*Cin, N)``. The row order is tap-major ``(kh, kw, c)``, the
    column order of ``im2col``'s patch matrix: HWIO's own, so the
    re-layout is a reshape."""
    kh, kw, cin, n = w.shape
    return np.asarray(w, np.float32).reshape(kh * kw * cin, n)


def gemm_block_k(backend: str, interpret: bool) -> int:
    """The K block ``_gemm`` launches the kernel with: ``KERNEL_BLOCK_K``
    compiled, one whole block for the ``ref`` backend and interpret mode."""
    return WHOLE_BLOCK if backend == "ref" or interpret else KERNEL_BLOCK_K


def padded_k(k: int, block_k: int) -> int:
    """``k`` rounded up to the kernel's K block, ``min(block_k, k)`` (the
    block ``ops.masked_matmul`` clamps to): unchanged while ``k`` fits in
    one block."""
    bk = min(block_k, k)
    return -(-k // bk) * bk


def im2col_builder(kernel: int) -> str:
    """Which of ``im2col``'s two builders a conv of this kernel size takes,
    by the ``jax.named_scope`` its patch ops carry in the program and its
    device trace: ``"im2col_slices"`` (one slice per tap) below
    ``TWO_STAGE_MIN_KERNEL``, ``"im2col_two_stage"`` from it up."""
    return ("im2col_two_stage" if kernel >= TWO_STAGE_MIN_KERNEL
            else "im2col_slices")


def im2col(x: jnp.ndarray, kernel: int, stride: int, padding: int,
           block_k: int = WHOLE_BLOCK) -> jnp.ndarray:
    """NHWC ``x`` -> the conv's im2col GEMM operand ``(N, Ho, Wo, K_pad)``,
    whose leading dims the GEMM flattens into its M rows for free.

    The activation is zero-padded spatially once, and its ``kernel**2``
    strided slices are concatenated tap-major along the channel axis:
    column ``(i*kw + j)*Cin + c`` holds channel ``c`` at tap ``(i, j)``,
    the row order of ``conv_weight_gemm_layout``. A large kernel
    (``im2col_builder``) takes two stages with the same result: its
    ``kw`` W-slices side by side in the lanes, then ``kh`` H-slices of
    that, so each slice moves ``kw`` times the channels. The values are
    ``conv_general_dilated_patches``' bit for bit, whose grouped identity
    convolution the TPU runs slowly and then relays out. ``K =
    kh*kw*Cin`` is padded with exact zero columns to ``padded_k(K,
    block_k)``, so the matrix is written once, already in the layout the
    compiled kernel reads; the caller pads the weight rows to match."""
    n, h, w, c = x.shape
    ho = (h + 2 * padding - kernel) // stride + 1
    wo = (w + 2 * padding - kernel) // stride + 1
    k = c * kernel * kernel
    k_pad = padded_k(k, block_k)
    builder = im2col_builder(kernel)
    with jax.named_scope(builder):
        xp = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                         (0, 0)))
        if builder == "im2col_two_stage":
            # the kernel's W taps side by side in the lanes, then its H
            # taps of that: 2*kernel slices in place of kernel**2
            xp = jnp.concatenate(
                [jax.lax.slice(xp, (0, 0, j, 0),
                               (n, xp.shape[1], j + stride * (wo - 1) + 1,
                                c), (1, 1, stride, 1))
                 for j in range(kernel)], axis=-1)
            taps = [jax.lax.slice(xp, (0, i, 0, 0),
                                  (n, i + stride * (ho - 1) + 1, wo,
                                   kernel * c), (1, stride, 1, 1))
                    for i in range(kernel)]
        else:
            taps = [jax.lax.slice(xp, (0, i, j, 0),
                                  (n, i + stride * (ho - 1) + 1,
                                   j + stride * (wo - 1) + 1, c),
                                  (1, stride, stride, 1))
                    for i in range(kernel) for j in range(kernel)]
        if k_pad > k:
            taps.append(jnp.zeros((n, ho, wo, k_pad - k), x.dtype))
        return jnp.concatenate(taps, axis=-1)


def quantize_params(params, cfg: CNNConfig,
                    policy: QuantPolicy) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Resolve the deployed (post-compaction) params into the quantized
    GEMM-layout bank ``quant_cnn_apply`` consumes: per conv/dense layer
    either ``{"wq", "scale", "zero", "b"}`` (quantized codes + affine
    qparams) or ``{"w", "b"}`` (fp32, ``weight_bits=None``), with conv
    weights already in im2col layout. Biases are never quantized (they
    are O(N) values the codec bound would dominate for nothing)."""
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for i, spec in enumerate(cfg.layers):
        if spec.kind not in ("conv", "dense"):
            continue
        p = params[f"l{i}"]
        w = np.asarray(p["w"], np.float32)
        if spec.kind == "conv":
            w = conv_weight_gemm_layout(w)
        b = jnp.asarray(p["b"], jnp.float32)
        if policy.weight_bits is None:
            out[f"l{i}"] = {"w": jnp.asarray(w), "b": b}
        else:
            codes, scale, zero = quantize_weights(
                w, policy.weight_bits, policy.per_channel)
            out[f"l{i}"] = {"wq": jnp.asarray(codes),
                            "scale": jnp.asarray(scale),
                            "zero": jnp.asarray(zero), "b": b}
    return out


def dequantize_weights(lp: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """The traced dequant: codes * scale + zero (broadcast over the
    output-channel axis), or the fp32 passthrough."""
    if "wq" in lp:
        return lp["wq"].astype(jnp.float32) * lp["scale"] + lp["zero"]
    return lp["w"]


def gemm_error_bound(x: jnp.ndarray, scale) -> jnp.ndarray:
    """Elementwise bound on ``|GEMM(x, dequant(w)) - GEMM(x, w)|``: each
    weight of output channel n is off by at most ``scale_n / 2`` (the
    affine codec contract), so output n errs by at most
    ``(scale_n / 2) * ||x_row||_1``. Shape broadcasts to ``(..., N)``;
    float32 accumulation adds only relative-eps slack on top."""
    s = jnp.atleast_1d(jnp.asarray(scale, jnp.float32))
    l1 = jnp.sum(jnp.abs(x), axis=-1, keepdims=True)
    return l1 * (s * 0.5)


# ---------------------------------------------------------------------------
# the kernel-dispatched forward
# ---------------------------------------------------------------------------
def _gemm(x: jnp.ndarray, w2: jnp.ndarray, mvec: jnp.ndarray,
          backend: str, interpret: bool) -> jnp.ndarray:
    if backend == "ref":
        return masked_matmul_ref(x, w2, mvec)
    if interpret:
        return masked_matmul(x, w2, mvec, block_m=WHOLE_BLOCK,
                             block_n=WHOLE_BLOCK,
                             block_k=gemm_block_k(backend, interpret),
                             interpret=True)
    return masked_matmul(x, w2, mvec,
                         block_k=gemm_block_k(backend, interpret))


def quant_cnn_apply(qparams, cfg: CNNConfig, x: jnp.ndarray,
                    masks: Optional[Dict[int, jnp.ndarray]] = None,
                    start_layer: int = 0, stop_layer: Optional[int] = None,
                    backend: str = "ref", interpret: bool = False):
    """``models.cnn.cnn_apply`` with conv/dense dispatched through the
    masked GEMM kernel over a ``quantize_params`` bank.

    The channel mask rides in the kernel's fused epilogue, and the bias
    is added pre-masked (``b * mask``) so the result matches the dense
    reference's ``(conv(x) + b) * mask`` exactly. relu / maxpool /
    flatten are the reference ops verbatim.
    """
    masks = masks or {}
    stop = stop_layer if stop_layer is not None else len(cfg.layers)
    for i in range(start_layer, stop):
        spec = cfg.layers[i]
        if spec.kind == "conv":
            lp = qparams[f"l{i}"]
            w2 = dequantize_weights(lp)          # (kh*kw*Cin, N)
            patches = im2col(x, spec.kernel, spec.stride, spec.padding,
                             gemm_block_k(backend, interpret))
            # both operands reach the kernel at the same K_pad: the
            # weights' pad rows meet the patches' zero columns
            w2 = jnp.pad(w2, ((0, patches.shape[-1] - w2.shape[0]), (0, 0)))
            mvec = (masks[i].astype(jnp.float32) if i in masks
                    else jnp.ones((w2.shape[1],), jnp.float32))
            x = _gemm(patches, w2, mvec, backend, interpret) + lp["b"] * mvec
        elif spec.kind == "relu":
            x = jax.nn.relu(x)
        elif spec.kind == "maxpool":
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max,
                (1, spec.kernel, spec.kernel, 1),
                (1, spec.stride, spec.stride, 1), "VALID")
        elif spec.kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif spec.kind == "dense":
            lp = qparams[f"l{i}"]
            w2 = dequantize_weights(lp)
            mvec = (masks[i].astype(jnp.float32) if i in masks
                    else jnp.ones((w2.shape[1],), jnp.float32))
            x = _gemm(x, w2, mvec, backend, interpret) + lp["b"] * mvec
    return x


# ---------------------------------------------------------------------------
# kernel-cost calibration (feeds latency_model.KernelCalibration)
# ---------------------------------------------------------------------------
def calibrate_quant_edge(qparams, cfg: CNNConfig, x,
                         masks: Optional[Dict[int, jnp.ndarray]] = None,
                         backend: str = "ref", interpret: bool = False,
                         repeats: int = 3):
    """Measure the quantized kernel path's per-layer wall-clock on this
    host -> ``KernelCalibration`` whose ``layer_s`` plugs straight into
    ``sweep_splits(..., measured_device_s=...)`` (Algorithm 1 line 22's
    timestamp hook, now over the *deployed* kernels instead of the fp32
    dense graph)."""
    from repro.core.partition.latency_model import KernelCalibration
    fns = [jax.jit(lambda v, s=i: quant_cnn_apply(
               qparams, cfg, v, masks=masks, start_layer=s,
               stop_layer=s + 1, backend=backend, interpret=interpret))
           for i in range(len(cfg.layers))]
    return KernelCalibration.measure(fns, jnp.asarray(x), repeats=repeats)
