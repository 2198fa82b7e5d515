"""Per-layer cost model + the collaborative-inference latency of Eq. 5:

    T(c) = T_D(c) + T_TX(c) + T_S(c)

Split point ``c`` means layers [0, c) run on the device and [c, N) on the
server; c = N is device-only, c = 0 is server-only (the raw input is
transmitted instead — the paper's 73.5 KB preprocessed tensor).

Two sources of per-layer numbers:
  * analytic — FLOPs and activation bytes from the layer specs (works for
    CNN and transformer configs alike; drives the dry-run-scale studies);
  * measured — wall-clock timestamps per layer (Algorithm 1 line 22), used
    by the Tier-A reproduction on this container's CPU.

Pruning feeds back into the model: masked channels shrink both FLOPs and
transmitted activation bytes (Fig. 4 of the paper).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CNNConfig, ModelConfig
from repro.core.partition.profiles import TwoTierProfile
from repro.models.cnn import cnn_apply, layer_shapes


@dataclass
class LayerCost:
    index: int
    name: str
    flops: float                # forward FLOPs for batch=1
    out_bytes: float            # activation bytes crossing a split AFTER it
    params_bytes: float = 0.0


# ---------------------------------------------------------------------------
# analytic costs: CNN
# ---------------------------------------------------------------------------
def cnn_layer_costs(cfg: CNNConfig,
                    masks: Optional[Dict[int, np.ndarray]] = None,
                    bytes_per_elem: int = 4) -> List[LayerCost]:
    shapes = layer_shapes(cfg)
    masks = masks or {}
    costs = []
    c_in = cfg.input_channels
    keep_in = 1.0
    flat = None
    for i, spec in enumerate(cfg.layers):
        keep_out = (float(np.mean(np.asarray(masks[i]))) if i in masks
                    else 1.0)
        if spec.kind == "conv":
            c_out, h, w = shapes[i]
            fl = 2.0 * h * w * c_out * c_in * spec.kernel ** 2
            fl *= keep_in * keep_out
            ob = h * w * c_out * keep_out * bytes_per_elem
            pb = (spec.kernel ** 2 * c_in * c_out * keep_in * keep_out
                  + c_out * keep_out) * bytes_per_elem
            costs.append(LayerCost(i, f"conv{i}", fl, ob, pb))
            c_in = c_out
            keep_in = keep_out
        elif spec.kind == "relu":
            shp = shapes[i]
            nelem = int(np.prod(shp)) * keep_in
            costs.append(LayerCost(i, f"relu{i}", nelem,
                                   nelem * bytes_per_elem))
        elif spec.kind == "maxpool":
            c, h, w = shapes[i]
            nelem = c * h * w * keep_in
            costs.append(LayerCost(i, f"pool{i}",
                                   nelem * spec.kernel ** 2,
                                   nelem * bytes_per_elem))
        elif spec.kind == "flatten":
            nelem = shapes[i][0] * keep_in
            costs.append(LayerCost(i, f"flat{i}", 0.0,
                                   nelem * bytes_per_elem))
        elif spec.kind == "dense":
            d_in = (flat if flat is not None else shapes[i - 1][0])
            fl = 2.0 * d_in * spec.features * keep_in * keep_out
            ob = spec.features * keep_out * bytes_per_elem
            pb = (d_in * spec.features * keep_in * keep_out
                  + spec.features * keep_out) * bytes_per_elem
            costs.append(LayerCost(i, f"fc{i}", fl, ob, pb))
            keep_in = keep_out
            flat = spec.features
    return costs


def cnn_input_bytes(cfg: CNNConfig, bytes_per_elem: int = 4) -> float:
    h, w = cfg.input_hw
    return h * w * cfg.input_channels * bytes_per_elem


def compacted_cnn_layer_costs(cfg: CNNConfig, masks,
                              bytes_per_elem: int = 4) -> List[LayerCost]:
    """Price the *deployed* network: pruned channels physically removed
    (``compact_cnn_config``), so FLOPs, activation bytes, and param bytes
    reflect the compacted shapes rather than masked-but-dense execution.
    Feed the result to ``greedy_split`` to re-pick the deployment split."""
    from repro.models.cnn import compact_cnn_config
    return cnn_layer_costs(compact_cnn_config(cfg, masks or {}),
                           bytes_per_elem=bytes_per_elem)


def quantized_cnn_layer_costs(cfg: CNNConfig, masks=None,
                              weight_bits: Optional[int] = 8,
                              bytes_per_elem: int = 4) -> List[LayerCost]:
    """Price the *quantized* deployed network: compacted shapes with
    ``params_bytes`` scaled to the quantized weight width — the traffic
    the int8/int4 edge actually streams from flash per inference. FLOPs
    and activation bytes are unchanged (weight-only quantization keeps
    fp32 activations). ``weight_bits=None`` prices the fp32 kernel
    path (identical to ``compacted_cnn_layer_costs``)."""
    costs = compacted_cnn_layer_costs(cfg, masks, bytes_per_elem)
    if weight_bits is None:
        return costs
    frac = weight_bits / (8.0 * bytes_per_elem)
    return [LayerCost(c.index, c.name, c.flops, c.out_bytes,
                      c.params_bytes * frac) for c in costs]


# ---------------------------------------------------------------------------
# analytic costs: transformer (per decoder layer, batch=1)
# ---------------------------------------------------------------------------
def transformer_layer_costs(cfg: ModelConfig, seq_len: int,
                            bytes_per_elem: int = 2,
                            decode: bool = False) -> List[LayerCost]:
    """Uniform per-layer cost; embedding/head folded into first/last."""
    d = cfg.d_model
    S = 1 if decode else seq_len
    ctx = seq_len
    costs = []
    kinds = cfg.layer_kinds()
    for i, kind in enumerate(kinds):
        fl = 0.0
        if kind in ("attn", "attn_dense", "moe"):
            if cfg.attention == "mla":
                m = cfg.mla
                qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                proj = (d * m.q_lora_rank + m.q_lora_rank * cfg.num_heads * qk
                        + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                        + m.kv_lora_rank * cfg.num_heads
                        * (m.qk_nope_head_dim + m.v_head_dim)
                        + cfg.num_heads * m.v_head_dim * d)
                att = cfg.num_heads * ctx * (qk + m.v_head_dim)
            else:
                proj = d * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * d
                win = min(ctx, cfg.sliding_window or ctx)
                att = cfg.num_heads * win * 2 * cfg.head_dim
            fl += 2.0 * S * (proj + att)
            if kind == "moe":
                m = cfg.moe
                mult = 3 if cfg.activation in ("silu_glu", "geglu") else 2
                fl += 2.0 * S * (m.top_k + m.num_shared) * d * m.d_expert * mult
                fl += 2.0 * S * d * m.num_experts     # router
            else:
                mult = 3 if cfg.activation in ("silu_glu", "geglu") else 2
                fl += 2.0 * S * d * cfg.d_ff * mult
        elif kind == "ssm":
            s = cfg.ssm
            d_in = cfg.d_inner
            proj = d * (2 * d_in + 2 * s.n_groups * s.d_state + cfg.ssm_heads)
            ssd = d_in * s.d_state * 6
            fl += 2.0 * S * (proj + ssd + d_in * d)
        out_bytes = S * d * bytes_per_elem
        costs.append(LayerCost(i, f"{kind}{i}", fl, out_bytes))
    return costs


# ---------------------------------------------------------------------------
# measured costs (Algorithm 1, line 22: "via timestamps")
# ---------------------------------------------------------------------------
def measure_cnn_layer_times(params, cfg: CNNConfig, x,
                            masks=None, repeats: int = 3) -> List[float]:
    """Wall-clock seconds per layer (jitted per-layer, CPU)."""
    times = []
    cur = x
    for i in range(len(cfg.layers)):
        fn = jax.jit(lambda v, p=params, s=i: cnn_apply(
            p, cfg, v, masks=masks, start_layer=s, stop_layer=s + 1))
        out = fn(cur)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(cur)
            jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / repeats)
        cur = out
    return times


@dataclass(frozen=True)
class KernelCalibration:
    """Measured per-layer edge seconds — the kernel-cost calibration hook
    of the split model. ``measure`` times any per-layer forward (fp32
    dense, kernel-dispatched, quantized — the caller passes the jitted
    layer callables, e.g. from ``repro.core.collab.quant
    .calibrate_quant_edge``), and ``layer_s`` plugs into
    ``split_latency`` / ``sweep_splits`` / ``energy_aware_split`` as
    ``measured_device_s``, so the sweep picks splits on the deployed
    kernels' real costs instead of the analytic roofline."""
    layer_s: tuple

    @classmethod
    def measure(cls, layer_fns: Sequence, x0,
                repeats: int = 3) -> "KernelCalibration":
        """``layer_fns[i]`` maps layer i's input to its output (jitted by
        the caller so the repeat loop times execution, not tracing);
        outputs thread forward so each layer is timed on its real input."""
        times = []
        cur = x0
        for fn in layer_fns:
            out = fn(cur)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(fn(cur))
            times.append(float((time.perf_counter() - t0) / repeats))
            cur = out
        return cls(tuple(times))

    def total_s(self, split: Optional[int] = None) -> float:
        """Measured device seconds for layers [0, split) (all when None)."""
        n = len(self.layer_s) if split is None else split
        return float(sum(self.layer_s[:n]))


def cnn_layer_output_bytes(params, cfg: CNNConfig, x, masks=None) -> List[int]:
    """True transmitted payload per split point: nonzero (surviving) units.

    Pruned channels are zeros under masked execution and are physically
    absent after compaction, so the honest wire size excludes them
    (paper Fig. 4 reports exactly this reduction)."""
    _, inter = cnn_apply(params, cfg, x, masks=masks,
                         return_intermediates=True)
    masks = masks or {}
    out = []
    shapes = layer_shapes(cfg)
    keep = 1.0
    for i, a in enumerate(inter):
        if i in masks:
            keep = float(np.mean(np.asarray(masks[i])))
        # relu/pool/flatten inherit the producer's surviving-channel ratio
        nbytes = a.nbytes / a.shape[0] * keep if keep < 1.0 else a.nbytes / a.shape[0]
        out.append(int(nbytes))
    return out


# ---------------------------------------------------------------------------
# the true wire payload at a split (codec x packing semantics of tx_scale)
# ---------------------------------------------------------------------------
def wire_tx_scale(cfg: CNNConfig, masks, split: int,
                  codec: Optional[str] = None, pack: bool = False,
                  compact: bool = False) -> float:
    """The ``tx_scale`` that makes the analytic ``tx_bytes`` equal the
    actual wire payload of the deployed runtime at ``split``.

    ``tx_scale`` is the product of two independent discounts:

      * **codec** — bytes per element relative to fp32 (1.0 / 0.5 / 0.25
        for fp32 / fp16 / int8, ``protocol.CODEC_TX_SCALE``);
      * **packing** — which elements ship at all. The masked layer costs
        (``cnn_layer_costs(cfg, masks)``) already price ``out_bytes`` at
        the surviving-channel fraction, which is the honest wire size only
        for ``pack=True`` (bitmask packing strips the dead channels) or
        ``compact=True`` (they are physically gone). A masked-but-dense
        deployment *without* packing ships the full tensor, zeros
        included, so this helper *un*-discounts by the keep ratio at the
        split boundary to match what actually crosses the link.

    Frame headers (a few tens of bytes) are not modelled.
    """
    from repro.core.collab.protocol import CODEC_TX_SCALE
    from repro.models.cnn import split_keep_indices
    scale = CODEC_TX_SCALE[codec or "fp32"]
    if compact or not masks or split <= 0:
        return scale
    keep = split_keep_indices(cfg, masks, split)
    if keep is None or pack:
        # all channels live, or the dead ones don't cross the wire: the
        # keep-discounted out_bytes already is the wire payload
        return scale
    n_full = layer_shapes(cfg)[split - 1][0]
    return scale * n_full / keep.size


# ---------------------------------------------------------------------------
# batched server time (what dynamic batching amortizes)
# ---------------------------------------------------------------------------
def _segment_time(costs: Sequence[LayerCost], idx, comp,
                  batch: int = 1) -> float:
    """Analytic time for layers ``idx`` on ``comp`` (a ComputeProfile):
    per-layer roofline (flops vs activation traffic) scaled by the batch
    plus the per-invocation overhead, paid once per layer per CALL. The
    single source of the formula — ``split_latency`` and
    ``batched_server_time`` must never drift apart."""
    t = 0.0
    for i in idx:
        work = max(batch * costs[i].flops / comp.flops_per_s,
                   2 * batch * costs[i].out_bytes / comp.mem_bw)
        t += work + comp.overhead_s
    return t


def batched_segment_time(costs: Sequence[LayerCost], start: int, stop: int,
                         comp, batch: int) -> float:
    """Analytic time for ONE invocation running layers ``[start, stop)``
    over ``batch`` fused rows on ``comp`` (a ``ComputeProfile``) — the
    same per-layer roofline + once-per-call overhead formula as
    ``split_latency``, exposed for *partial* stacks: the fleet
    simulator's cloudlet tier runs ``[c1, c2)`` and its cloud tier
    ``[c2, N)``, both priced here so tier numbers can never drift from
    the two-tier model."""
    if batch < 1:
        raise ValueError("batch must be >= 1")
    if not 0 <= start <= stop <= len(costs):
        raise ValueError(f"segment [{start}, {stop}) outside "
                         f"[0, {len(costs)}]")
    return _segment_time(costs, range(start, stop), comp, batch)


def batched_server_time(costs: Sequence[LayerCost], c: int,
                        server, batch: int) -> float:
    """Analytic T_S for ONE cloud invocation serving ``batch`` fused
    requests on ``server`` (a ``ComputeProfile``): per-layer FLOPs and
    activation traffic scale with the batch, but the per-invocation
    constant (``ComputeProfile.overhead_s`` — kernel launch, dispatch,
    framework overhead) is paid once per *batch* instead of once per
    *request*. The gap between ``batch * batched_server_time(..., 1)``
    and ``batched_server_time(..., batch)`` is exactly the throughput
    headroom the cross-client dynamic batching engine recovers; per
    request it approaches ``overhead_s``-free compute as the batching
    window fills."""
    return batched_segment_time(costs, c, len(costs), server, batch)


# ---------------------------------------------------------------------------
# Eq. 5: the latency of a split
# ---------------------------------------------------------------------------
def split_latency(costs: Sequence[LayerCost], c: int,
                  profile: TwoTierProfile,
                  input_bytes: float,
                  measured_device_s: Optional[Sequence[float]] = None,
                  measured_server_s: Optional[Sequence[float]] = None,
                  tx_scale: float = 1.0,
                  round_trip: bool = False
                  ) -> Dict[str, float]:
    """Latency breakdown for split point c (layers [0,c) on device).

    ``tx_scale`` discounts the bytes that actually cross the link relative
    to the masked/compacted fp32 activation the costs were priced at. It
    composes the feature codec (0.5 for fp16, 0.25 for int8 — see
    ``repro.core.collab.protocol.CODEC_TX_SCALE``) with the channel-packing
    correction; use ``wire_tx_scale`` to derive the combined factor for a
    concrete deployment. Compute-side memory traffic is unaffected.

    **T_TX is uplink-only by default**: it charges the feature tensor
    (device -> server) plus ONE RTT, matching the paper's Eq. 5. The
    socket path is actually request/response — logits come back — so
    ``round_trip=True`` adds the return payload (the final layer's output
    bytes) and a second RTT for deployments where the downlink is not
    negligible. ``tx_bytes`` in the returned row stays uplink-only either
    way (it is what the runtimes report as transmitted feature bytes)."""
    n = len(costs)
    assert 0 <= c <= n

    def seg_time(idx, comp, measured):
        if measured is not None:
            return sum(measured[i] for i in idx)
        return _segment_time(costs, idx, comp)

    t_d = seg_time(range(c), profile.device, measured_device_s)
    t_s = seg_time(range(c, n), profile.server, measured_server_s)
    tx_bytes = (input_bytes if c == 0 else costs[c - 1].out_bytes) * tx_scale
    if c == n:
        t_tx = 0.0
    else:
        t_tx = tx_bytes / profile.link.bandwidth + profile.link.rtt_s
        if round_trip:
            # logits downlink: final layer output + its own RTT
            t_tx += (costs[n - 1].out_bytes / profile.link.bandwidth
                     + profile.link.rtt_s)
    return {"T_D": t_d, "T_TX": t_tx, "T_S": t_s,
            "T": t_d + t_tx + t_s, "tx_bytes": 0.0 if c == n else tx_bytes}
