"""Collaborative split-inference executors (paper §3.3 deployment).

.. note::
   **Internal layer.** The public front door for deployment is
   ``repro.serving``: build a ``DeploymentPlan`` (the serializable
   deployment contract) and open an ``InferenceSession`` via
   ``serving.connect(plan, backend="local"|"socket"|"streaming")``. The
   raw constructors below (``CollabRunner``, ``serve_cloud``,
   ``EdgeClient``, ``build_split_fns``) remain importable as thin
   compatibility shims but are considered internal/deprecated as direct
   entry points — they take the deployment contract as loose positional
   knobs and perform no peer-agreement check.

``CollabRunner`` — in-process: edge submodel -> (shaped) channel -> cloud
submodel, with the Eq. 5 timing breakdown measured per request. This is the
engine behind benchmarks fig5 and the Gradio-replacement CLI demo.

``serve_cloud`` / ``EdgeClient`` — real localhost TCP sockets with the
token-bucket shaper, mirroring the paper's socket deployment: the edge sends
the intermediate feature tensor, the cloud returns class logits.

The *compacted deployment path* (``compact=True``): pruning masks are
materialized via ``compact_params`` before the edge/cloud submodels are
jitted, so the deployed network is physically smaller — real FLOP and
wire-byte reduction rather than zeroed channels. The *feature codec*
(``codec=`` fp32 | fp16 | int8, plus mask-aware channel ``pack``-ing for
masked-but-dense deployments) shrinks T_TX bytes 2-4x; each frame carries
its own codec header, so the cloud decodes whatever each edge picked
per-frame (``decode_any``) with no connection-level handshake.

For overlapped (pipelined) streaming service of many requests, see
``repro.core.collab.streaming.StreamingCollabRunner`` (in-process) and
``EdgeClient.submit``/``collect`` (async socket path).

*Cross-client dynamic batching* (``serve_cloud(batching=...)``): handler
threads stop invoking the cloud sub-model per frame and submit decoded
features to the ``DynamicBatcher`` (``repro.core.collab.batching``) —
per-lane queues, a short batching window, power-of-two bucket padding,
ONE row-mapped jitted cloud call per fused batch, logits bit-identical
to the unbatched path. All connections of one server also share ONE
``LinkShaper`` token bucket for the bytes the server transmits, so N
concurrent edges contend for the modeled downlink instead of each
getting a private copy of it (each edge's uplink is still paced by that
edge's own radio — its private shaper).

*Adaptive split switching*: every executor resolves its sub-model
functions through a ``SplitFnBank`` — one deployed parameter set, a
jitted (edge_fn, cloud_fn) pair per candidate split — so changing the
split point at run time is a dictionary lookup, not a redeploy. The
socket pair switches live via the RESPLIT control frame
(``EdgeClient.resplit``): the edge announces the new split, the cloud
swaps its ``start_layer`` on the same connection, and the next request
already flows at the new partition. The decision logic (bandwidth
estimation + hysteresis) lives in ``repro.core.collab.adaptive``.

``tx_bytes`` is the transmitted frame *payload* in bytes — identical
across CollabRunner, EdgeClient, and the streaming runtime for the same
deployment; the socket executors' 8-byte length prefix is framing, not
payload, and is excluded.

*Host spans* of the socket path (``jax.profiler.TraceAnnotation``, on the
same clock as the device's operations while a profiler trace runs, one
cheap check each otherwise): ``repro.edge.forward``, ``repro.edge.encode``
and ``repro.edge.decode`` on the edge (the first two with the ``rows``
of the frame they make), ``repro.cloud.decode`` and ``repro.cloud.encode``
in the server, and the batcher's ``repro.batcher.fill`` and
``repro.cloud.call``. None wraps a blocking
socket read or the wait for a queued item, except that ``fill`` holds the
batch window by design.
"""
from __future__ import annotations

import functools
import queue
import socket
import struct
import threading
import time
from concurrent.futures import CancelledError, Future
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CNNConfig
from repro.core.collab.batching import (BatchingPolicy, DynamicBatcher,
                                        LaneSaturated, next_pow2_bucket,
                                        pad_rows)
from repro.core.collab.channel import (FaultInjector, LinkShaper,
                                       ShapedSocket, SimChannel,
                                       apply_send_fault, recv_exact)
from repro.core.collab.cluster import FleetExhaustedError, FleetRouter
from repro.core.collab.faults import (FaultPolicy, RequestTimeout,
                                      ServerBusy, ServerDraining,
                                      fault_record)
from repro.core.collab.protocol import (CAP_CRC, CODEC_TX_SCALE,
                                        PROTOCOL_VERSION,
                                        FrameIntegrityError,
                                        PlanMismatchError, decode_any,
                                        decode_busy, decode_drain,
                                        decode_heartbeat, decode_hello,
                                        decode_resplit,
                                        decode_sealed, decode_tensor,
                                        encode_busy, encode_drain,
                                        encode_feature, encode_heartbeat,
                                        encode_hello, encode_resplit,
                                        encode_sealed, encode_tensor,
                                        frame_lane, hello_caps, is_busy,
                                        is_drain, is_heartbeat,
                                        is_hello, is_resplit, is_sealed)
from repro.core.partition.profiles import (LinkProfile, LinkTrace,
                                           TwoTierProfile)
from repro.models.cnn import (cnn_apply, compact_params, split_keep_indices)


@dataclass
class RequestTiming:
    """Per-request accounting: ``t_*`` in seconds, ``tx_bytes`` the
    transmitted frame payload in bytes, ``e_edge_j`` the edge device's
    energy in joules (None on an un-metered deployment — i.e. no
    ``EnergyProfile`` attached)."""
    t_device: float
    t_tx: float
    t_server: float
    tx_bytes: int
    e_edge_j: Optional[float] = None

    @property
    def total(self) -> float:
        return self.t_device + self.t_tx + self.t_server


def _frame_io(sock: socket.socket, ch: Optional[ShapedSocket]):
    """(recv_exact, sendall) pair for a connection, shaped or raw."""
    rx = ch.recv_exact if ch else (lambda k: recv_exact(sock, k))
    tx = ch.sendall if ch else sock.sendall
    return rx, tx


def deploy_submodels(params, cfg: CNNConfig, masks=None,
                     compact: bool = False):
    """Resolve the deployed (params, cfg, masks) triple.

    ``compact=True`` materializes the pruning masks via ``compact_params``:
    the returned network is physically smaller and needs no masks at run
    time. Both peers of a split deployment must agree on this flag (the
    split-boundary tensor has compacted channel count)."""
    if compact:
        if not masks:
            raise ValueError(
                "compact=True requires pruning masks: a dense model has "
                "nothing to compact (pass compact=False, or provide the "
                "masks the plan was pruned with)")
        cparams, ccfg = compact_params(params, cfg, masks)
        return cparams, ccfg, None
    return params, cfg, masks


def half_params(params: Dict, split: int, side: str) -> Dict:
    """The layers one half of ``split`` reads: ``[0, split)`` on the
    ``"edge"``, ``[split, N)`` in the ``"cloud"``."""
    edge = side == "edge"
    return {k: v for k, v in params.items() if (int(k[1:]) < split) == edge}


def split_step(cfg: CNNConfig, split: int, side: str, masks=None,
               quant_backend: Optional[Tuple[str, bool]] = None,
               batched: bool = False) -> Callable:
    """The pure ``(params, x) -> y`` step of one half of ``split`` over
    ``half_params``: the edge runs layers ``[0, split)`` — through the
    masked-GEMM kernel path over quantized params when
    ``quant_backend`` (``resolve_backend``'s pair) is given — and the
    cloud runs ``[split, N)`` fp32 dense. ``batched`` maps the batch-1
    computation over the leading axis (``jax.lax.map``) so each row is
    bit-identical to the batch-1 step — which a free reshape-to-batched
    conv would NOT guarantee (XLA may re-associate reductions under a
    different batch shape) — and the dynamic batching engine can
    promise batched == sequential logits exactly."""
    if quant_backend is not None:
        from repro.core.collab.quant import quant_cnn_apply
        backend, interpret = quant_backend

        def run(p, x):
            return quant_cnn_apply(p, cfg, x, masks=masks, stop_layer=split,
                                   backend=backend, interpret=interpret)
    elif side == "edge":
        def run(p, x):
            return cnn_apply(p, cfg, x, masks=masks, stop_layer=split)
    else:
        def run(p, x):
            return cnn_apply(p, cfg, x, masks=masks, start_layer=split)

    if not batched:
        return run
    return lambda p, x: jax.lax.map(lambda row: run(p, row[None])[0], x)


class SplitFnBank:
    """Jitted edge/cloud sub-model functions for *every* candidate split
    of one deployed network.

    The deployment (params, cfg, masks, compaction) is resolved once; each
    split's (edge_fn, cloud_fn, keep) triple is built on first request and
    cached, so an adaptive controller can switch splits mid-run with a
    dictionary lookup instead of a redeploy. Both peers of a socket
    deployment hold a bank over the same params, which is what makes the
    RESPLIT frame sufficient to move the partition without reconnecting.

    The deployed (and quantized) params are placed on the device once and
    reach every executable as *arguments* (``split_step`` bound to
    ``half_params``): a closed-over array would be lowered as an HLO
    constant, copying the weights into each (split, bucket) executable
    and its compile time.
    """

    def __init__(self, params, cfg: CNNConfig, masks=None,
                 compact: bool = False, pack: bool = False, quant=None):
        (dparams, self.deploy_cfg,
         self.dmasks) = deploy_submodels(params, cfg, masks, compact)
        self.dparams = jax.device_put(dparams)
        self.pack = pack
        self.compact = compact
        #: optional ``QuantPolicy`` — when set, the EDGE half of every
        #: split dispatches conv/dense through the masked-GEMM kernel
        #: over (possibly int8/int4-quantized) deployed weights; cloud
        #: halves stay fp32 dense (the server is not the device the
        #: paper quantizes for). Resolved eagerly so bank construction
        #: owns all quant state and the built functions stay read-only.
        self.quant = quant
        if quant is not None:
            from repro.core.collab.quant import (quantize_params,
                                                 resolve_backend)
            self._qparams = jax.device_put(quantize_params(
                self.dparams, self.deploy_cfg, quant))
            self._q_backend = resolve_backend(quant)
        self.n_layers = len(self.deploy_cfg.layers)
        #: (split, batched) -> (edge_fn, cloud_fn, keep)
        self._fns: Dict[Tuple[int, bool], Tuple] = {}
        # serve_cloud handler threads share one bank: first-touch builds
        # of a (split, bucket) pair must not race the dict insert
        self._cache_lock = threading.Lock()
        #: traced-body counter — bumps once every time XLA (re)traces any
        #: sub-model function of this bank (a new split, a new batch
        #: bucket shape). ``warm`` followed by a steady count is the
        #: no-recompilation-in-steady-state regression guard.
        self.n_traces = 0

    def _build(self, split: int, batched: bool) -> Tuple:
        def bind(side: str):
            quantized = side == "edge" and self.quant is not None
            step = split_step(self.deploy_cfg, split, side, self.dmasks,
                              self._q_backend if quantized else None,
                              batched)

            def traced(p, x):
                self.n_traces += 1      # runs at trace time only
                return step(p, x)

            # the program's name in the profiler's module line:
            # jit_edge_c13, jit_cloud_c13_rows
            traced.__name__ = traced.__qualname__ = (
                f"{side}_c{split}" + ("_rows" if batched else ""))
            src = self._qparams if quantized else self.dparams
            return functools.partial(jax.jit(traced),
                                     half_params(src, split, side))

        edge_fn = bind("edge") if split > 0 else None
        cloud_fn = bind("cloud") if split < self.n_layers else None
        keep = (split_keep_indices(self.deploy_cfg, self.dmasks, split)
                if self.pack and not self.compact else None)
        return edge_fn, cloud_fn, keep

    def get(self, split: int, batch_bucket: Optional[int] = None):
        """(edge_fn, cloud_fn, keep) for ``split``; fns are None at the
        c=0 / c=N extremes. ``keep`` is the surviving-channel index set
        for the wire codec's packing — only set for masked-but-dense
        deployments (after compaction the dead channels are already gone
        from the tensor).

        ``batch_bucket`` selects the *bucketed* compilation cache: the
        returned pair is the row-mapped batched variant meant to be
        called at exactly that (padded) leading-axis size — one compiled
        executable per (split, bucket) shape, bit-identical per row to
        the batch-1 pair. ``None`` keeps the historical batch-1 pair."""
        if not 0 <= split <= self.n_layers:
            raise ValueError(f"split {split} outside [0, {self.n_layers}]")
        if batch_bucket is not None and batch_bucket < 1:
            raise ValueError(f"batch_bucket must be >= 1, got {batch_bucket}")
        key = (split, batch_bucket is not None)
        with self._cache_lock:
            if key not in self._fns:
                self._fns[key] = self._build(*key)
            return self._fns[key]

    def warm(self, splits: Sequence[int], image: np.ndarray,
             edge_only: bool = False, buckets: Sequence[int] = (1,),
             cloud_only: bool = False) -> None:
        """Pre-jit (trace + compile) the edge/cloud pair of each candidate
        split by pushing one sample through, so a mid-run switch does not
        stall the first request at the new partition. ``edge_only`` skips
        compiling the cloud halves (the edge peer of a socket deployment
        never runs them).

        ``buckets`` additionally warms the row-mapped batched pair at
        each listed leading-axis size > 1 (splits x buckets), so a
        dynamic-batching server's first burst — or its first burst after
        a live RESPLIT — never stalls on tracing. ``cloud_only`` skips
        the batched *edge* halves there (the batching server executes
        only cloud sub-models; its batch-1 edge half still runs once to
        derive the split-boundary feature shape)."""
        for c in splits:
            edge_fn, cloud_fn, _ = self.get(c)
            feat = jnp.asarray(image)        # split-boundary tensor at c
            if edge_fn is not None:
                feat = edge_fn(feat)
                jax.block_until_ready(feat)
            if cloud_fn is not None and not edge_only:
                jax.block_until_ready(cloud_fn(feat))
            for b in buckets:
                if b <= 1:
                    continue
                edge_b, cloud_b, _ = self.get(c, batch_bucket=b)
                if edge_b is not None and not cloud_only:
                    tile = np.repeat(np.asarray(image), b, axis=0)
                    jax.block_until_ready(edge_b(jnp.asarray(tile)))
                if cloud_b is not None and not edge_only:
                    fb = np.repeat(np.asarray(feat), b, axis=0)
                    jax.block_until_ready(cloud_b(jnp.asarray(fb)))


def _warm_input(cfg: CNNConfig) -> np.ndarray:
    """A zero batch-1 sample at the model's input shape, for pre-jitting."""
    h, w = cfg.input_hw
    return np.zeros((1, h, w, cfg.input_channels), np.float32)


def build_split_fns(params, cfg: CNNConfig, split: int, masks=None,
                    compact: bool = False, pack: bool = False, quant=None):
    """One-stop deployment resolution shared by every executor: returns
    (edge_fn, cloud_fn, keep, deploy_cfg) for the given split (one-shot
    wrapper over ``SplitFnBank``)."""
    bank = SplitFnBank(params, cfg, masks, compact, pack, quant=quant)
    edge_fn, cloud_fn, keep = bank.get(split)
    return edge_fn, cloud_fn, keep, bank.deploy_cfg


class CollabRunner:
    """In-process split executor with simulated (or real-time) channel.

    ``compact`` deploys physically-pruned submodels; ``codec``/``pack``
    select the wire encoding of the split-boundary feature tensor (the
    payload is genuinely encoded and decoded, so lossy codecs see their
    true numerical effect and tx_bytes is the true frame size).
    """

    def __init__(self, params, cfg: CNNConfig, split: int,
                 profile: TwoTierProfile, masks=None,
                 realtime_channel: bool = False,
                 simulate_compute: bool = True,
                 compact: bool = False, codec: Optional[str] = None,
                 pack: bool = False, trace: Optional[LinkTrace] = None,
                 energy=None, faults: Optional[FaultInjector] = None,
                 quant=None):
        self.cfg = cfg
        self.split = split
        self.profile = profile
        self.masks = masks
        self.codec = codec
        self.compact = compact
        self.pack = pack
        self.channel = SimChannel(profile.link, realtime=realtime_channel,
                                  trace=trace, faults=faults)
        self.simulate_compute = simulate_compute
        #: optional ``EnergyProfile`` — when set, every RequestTiming
        #: carries ``e_edge_j`` (joules) priced from the same breakdown
        #: the timing reports (one formula for analytic and measured)
        self.energy = energy
        self._bank = SplitFnBank(params, cfg, masks, compact, pack,
                                 quant=quant)
        self.deploy_cfg = self._bank.deploy_cfg
        self.set_split(split)

    def _timing(self, t_device: float, t_tx: float, t_server: float,
                tx_bytes: int) -> RequestTiming:
        """Assemble one request's accounting record, energy-priced when
        the runner carries an ``EnergyProfile`` (RTT peeled off the
        uplink term and billed as waiting, per ``energy_breakdown``)."""
        e = (self.energy.request_energy(t_device, t_tx, t_server,
                                        rtt_s=self.profile.link.rtt_s)
             if self.energy is not None else None)
        return RequestTiming(t_device, t_tx, t_server, tx_bytes,
                             e_edge_j=e)

    def warm(self, splits: Sequence[int]) -> None:
        """Pre-jit every candidate's edge/cloud pair (batch-1 shape) so an
        adaptive switch doesn't stall its first request on compilation."""
        self._bank.warm(splits, _warm_input(self.cfg))

    def set_split(self, split: int) -> None:
        """Move the partition point (adaptive re-split): swap in the
        bank's jitted pair for ``split`` and re-price the analytic
        breakdown. The channel (and its virtual trace clock) carries over
        — the link doesn't reset because the deployment re-planned."""
        self._edge_fn, self._cloud_fn, self._keep = self._bank.get(split)
        self.split = split
        # analytic compute-time model for reporting at the paper's hardware
        from repro.core.partition.latency_model import (
            cnn_layer_costs, compacted_cnn_layer_costs, split_latency,
            cnn_input_bytes, wire_tx_scale)
        costs = (compacted_cnn_layer_costs(self.cfg, self.masks)
                 if self.compact else cnn_layer_costs(self.cfg, self.masks))
        # tx_scale composes the codec discount with the packing correction
        # so the analytic tx_bytes equals the measured wire payload
        self._analytic = split_latency(
            costs, split, self.profile, cnn_input_bytes(self.cfg),
            tx_scale=wire_tx_scale(self.cfg, self.masks, split,
                                   codec=self.codec, pack=self.pack,
                                   compact=self.compact))

    def _encode(self, x: np.ndarray) -> bytes:
        if self.codec is None and self._keep is None:
            return x.tobytes()          # legacy raw-payload accounting
        return encode_feature(x, codec=self.codec or "fp32",
                              keep=self._keep if x.ndim > 1 else None)

    def infer(self, image: np.ndarray) -> Dict:
        """image (B, H, W, C). Returns logits + RequestTiming.

        Wall-clock is measured for the actual CPU compute; the *reported*
        device/server terms come from the analytic profile when
        ``simulate_compute`` (the container has no i7/3090 pair), while the
        channel term is always charged per transmitted byte.
        """
        x = jnp.asarray(image)
        t0 = time.perf_counter()
        if self._edge_fn is not None:
            x = self._edge_fn(x)
            jax.block_until_ready(x)
        t1 = time.perf_counter()
        # a trace-driven channel keeps degrading during compute, so the
        # virtual clock must advance across the device time too
        if self.channel.trace is not None:
            self.channel.advance(self._analytic["T_D"] if
                                 self.simulate_compute else t1 - t0)
        if self._cloud_fn is not None:
            buf = self._encode(np.asarray(x))
            tx_bytes = len(buf)
            t_tx = self.channel.send(tx_bytes)
            if self.codec is not None or self._keep is not None:
                x = jnp.asarray(decode_any(buf)[0])
        else:
            tx_bytes, t_tx = 0, 0.0
        t2 = time.perf_counter()
        out = x
        if self._cloud_fn is not None:
            out = self._cloud_fn(x)
            jax.block_until_ready(out)
        t3 = time.perf_counter()
        if self.channel.trace is not None:
            self.channel.advance(self._analytic["T_S"] if
                                 self.simulate_compute else t3 - t2)
        if self.simulate_compute:
            timing = self._timing(self._analytic["T_D"], t_tx,
                                  self._analytic["T_S"], tx_bytes)
        else:
            timing = self._timing(t1 - t0, t_tx, t3 - t2, tx_bytes)
        # ARQ accounting from the channel: lost copies were retransmitted
        # by the modeled link layer, so the request was still served
        evs = (self.channel.last_send_events
               if self._cloud_fn is not None else ())
        return {"logits": np.asarray(out), "timing": timing,
                "wallclock": {"edge": t1 - t0, "cloud": t3 - t2},
                "fault": fault_record(
                    faults=len(evs),
                    retries=sum(1 for e in evs if e != "stall"))}

    def infer_batch(self, images: Sequence[np.ndarray],
                    bucket: Optional[int] = None) -> List[Dict]:
        """Serve a batch of requests through ONE edge call and ONE cloud
        call (the local fast path behind ``InferenceSession.infer_many``
        on a plan with a ``batching`` section).

        Results are **bit-identical per row** to batch-1 execution:
        compute is the bank's row-mapped batched pair (the batch-1
        computation mapped over rows, padded to ``bucket`` — next power
        of two by default — to bound recompilation), so single-row
        requests — the runtimes' standard granularity — match sequential
        ``infer`` bitwise. (A multi-row request's rows are computed as
        batch-1 rows; sequential ``infer`` of the same request would run
        one fused batch-B conv, which XLA does not keep bit-stable
        across batch shapes.) The wire step encodes/charges one frame
        *per request* exactly as the sequential loop does (per-frame
        int8 quantization scales and ``tx_bytes`` accounting are
        unchanged; only the dispatches are amortized)."""
        n = len(images)
        if n == 0:
            return []
        arrs = [np.asarray(im) for im in images]
        counts = [a.shape[0] for a in arrs]       # a request may be B rows
        offs = np.concatenate([[0], np.cumsum(counts)])
        rows = int(offs[-1])
        bucket = bucket or next_pow2_bucket(rows)
        if bucket < rows:
            raise ValueError(f"bucket {bucket} smaller than batch of "
                             f"{rows} rows")
        edge_b, cloud_b, _ = self._bank.get(self.split, batch_bucket=bucket)
        xs = pad_rows(np.concatenate(arrs, axis=0), bucket)
        t0 = time.perf_counter()
        feats = jnp.asarray(xs)
        if edge_b is not None:
            feats = edge_b(feats)
            jax.block_until_ready(feats)
        t1 = time.perf_counter()
        if self.channel.trace is not None:
            self.channel.advance(self._analytic["T_D"] if
                                 self.simulate_compute else t1 - t0)
        feats_np = np.asarray(feats)
        per_req: List[Tuple[int, float, Tuple[str, ...]]] = []
        if cloud_b is not None:
            decoded_frames = []
            for i in range(n):           # one frame per request, as infer()
                frame = feats_np[offs[i]:offs[i] + counts[i]]
                buf = self._encode(frame)
                t_tx = self.channel.send(len(buf))
                per_req.append((len(buf), t_tx,
                                self.channel.last_send_events))
                decoded_frames.append(decode_any(buf)[0]
                                      if (self.codec is not None
                                          or self._keep is not None)
                                      else frame)
            decoded = pad_rows(np.concatenate(
                [np.asarray(r) for r in decoded_frames], axis=0), bucket)
            t2 = time.perf_counter()
            out = cloud_b(jnp.asarray(decoded))
            jax.block_until_ready(out)
            t3 = time.perf_counter()
        else:
            per_req = [(0, 0.0, ())] * n
            t2 = t3 = time.perf_counter()
            out = feats
        if self.channel.trace is not None:
            self.channel.advance(self._analytic["T_S"] if
                                 self.simulate_compute else t3 - t2)
        out = np.asarray(out)
        results = []
        for i in range(n):
            nbytes, t_tx, evs = per_req[i]
            if self.simulate_compute:
                timing = self._timing(self._analytic["T_D"], t_tx,
                                      self._analytic["T_S"], nbytes)
            else:
                timing = self._timing((t1 - t0) / n, t_tx,
                                      (t3 - t2) / n, nbytes)
            results.append({"logits": out[offs[i]:offs[i] + counts[i]],
                            "timing": timing,
                            "wallclock": {"edge": t1 - t0,
                                          "cloud": t3 - t2},
                            "fault": fault_record(
                                faults=len(evs),
                                retries=sum(1 for e in evs
                                            if e != "stall"))})
        return results


# ---------------------------------------------------------------------------
# real-socket deployment (localhost stand-in for the paper's Wi-Fi pair)
# ---------------------------------------------------------------------------
def serve_cloud(params, cfg: CNNConfig, split: int, port: int,
                masks=None, link: Optional[LinkProfile] = None,
                max_requests: Optional[int] = None,
                ready: Optional[threading.Event] = None,
                compact: bool = False, host: str = "127.0.0.1",
                max_clients: Optional[int] = 1,
                stop: Optional[threading.Event] = None,
                plan_digest: Optional[str] = None,
                resplit_candidates: Optional[Sequence[int]] = None,
                trace: Optional[LinkTrace] = None,
                batching: Optional[BatchingPolicy] = None,
                batch_stats: Optional[Dict] = None,
                simulate_server=None,
                fault_policy: Optional[FaultPolicy] = None,
                faults: Optional[FaultInjector] = None,
                fault_stats: Optional[Dict] = None,
                die: Optional[threading.Event] = None,
                drain: Optional[threading.Event] = None,
                quant=None,
                on_engine: Optional[Callable[[DynamicBatcher], None]] = None
                ) -> None:
    """Cloud-side loop: accept edge connections, answer frames.

    A threaded accept loop serves each connection in its own handler
    thread, so one cloud process serves many edges concurrently.
    ``max_clients`` bounds how many connections are accepted before the
    loop drains and returns (default 1 — the paper's single-edge
    deployment and the historical behaviour); ``None`` accepts until the
    ``stop`` event is set. ``max_requests`` is a per-connection limit.

    All connections draw tokens from ONE ``LinkShaper`` (one token bucket
    per server): N concurrent edges contend for the server's modeled
    transmit path (the logits downlink) instead of each connection
    getting a private copy of it. Uplink pacing stays per-edge — each
    edge device shapes its own sends with its own radio's bucket; what
    this fixes is the server side multiplying ITS link by the number of
    connections.

    Frames are decoded via ``decode_any``: the edge negotiates the codec
    per frame through the frame header (raw fp32, fp16, int8, packed), so
    a single server loop accepts them all. ``compact=True`` serves the
    physically-pruned submodel (the connecting edge must match).

    ``plan_digest`` arms the HELLO handshake: an edge that opens with a
    HELLO frame has its plan digest compared against ours, and a mismatch
    is answered with a reject status before the connection closes — the
    contract check behind ``repro.serving``. Edges that skip the HELLO
    (legacy clients) are served unchecked.

    A RESPLIT control frame moves the connection's split point live: the
    handler swaps its cloud sub-model (``SplitFnBank`` lookup — the bank
    holds every candidate over the same deployed params) and acks, all on
    the same connection. Split state is per-connection, so concurrent
    edges can sit at different partitions. ``resplit_candidates``
    restricts which splits are accepted (the plan's adaptive section);
    ``None`` accepts any split valid for the deployed network.
    ``trace`` makes the shaper's rate follow a time-varying link.

    ``batching`` arms the cross-client dynamic batching engine
    (``repro.core.collab.batching``): handlers submit decoded feature
    tensors to per-lane queues instead of running ``cloud_fn`` per frame,
    a per-connection writer thread ships responses back in order while
    the handler keeps reading (so one pipelining edge can fill a batch by
    itself), and ONE bucketed jitted cloud call serves each fused batch —
    with logits bit-identical per row to batch-1 execution (i.e. the
    unbatched path, for the standard single-row frames; a multi-row
    frame's rows are computed as batch-1 rows, not one fused conv, and a
    frame wider than ``max_batch`` bypasses the engine). ``batch_stats``
    (a dict) receives the engine's per-lane accounting when the server
    shuts down; ``on_engine`` is called with the engine once it is built,
    before the server listens, so a caller can read its live counters.

    ``simulate_server`` (a ``ComputeProfile``) charges every cloud
    invocation the analytic ``batched_server_time`` on that hardware,
    serialized server-wide — the modeled accelerator executes one batch
    at a time, like ``CollabRunner``'s ``simulate_compute`` this
    container stands in for. Colocated benchmarks use it to measure the
    batching engine against the paper's 3090 rather than against this
    host's core count (on which N real batch-1 calls may parallelize in
    ways the target device cannot). Real compute still runs first, so
    logits and bit-identity are unaffected.

    Fault tolerance: an edge whose HELLO advertises ``CAP_CRC`` gets
    sealed (CRC32 + sequence-number) data frames both ways — a
    corrupted request surfaces as ``FrameIntegrityError`` and closes
    the connection (the edge retries on a fresh one), and every data
    response echoes the request's sequence number so a reconnecting
    edge can replay and match. ``fault_policy`` (the plan's ``faults``
    section) arms idle-client reaping: a connection silent for
    ``3 * heartbeat_s`` is closed (edges send HEARTBEAT keepalives
    between requests to stay alive). Setting ``stop`` now performs a
    *graceful drain* — handlers stop reading but flush every queued /
    batched response before closing — while the ``die`` event is the
    crash lever (connections dropped mid-frame, for fault drills).
    ``faults`` injects the schedule's faults into this server's *data
    responses* (drop/corrupt/stall/disconnect, plus ``die`` = kill the
    whole server); ``fault_stats`` (a dict) receives classified error
    counters (``reaped_conns``, ``integrity_errors``, ``conn_errors``,
    ``bad_frames``, ``writer_errors``, ``abandoned_futures``,
    ``heartbeats``, ``busy_shed``, ``drain_redirects``) at shutdown.

    Fleet membership: the ``drain`` event is the rolling-restart lever —
    once set, every *new* data request is answered with a DRAIN control
    frame instead of being served (in-flight batched work still
    completes), telling fleet-routed edges to migrate to another member
    mid-session with zero failed requests; ``stop`` afterwards flushes
    and exits as usual. With a bounded batching lane
    (``BatchingPolicy.max_queue``), a request that would overflow the
    lane queue is answered with a BUSY backpressure frame (shed reason
    ``"queue"``, mirroring the fleet simulator's admission vocabulary)
    instead of stalling the connection.
    """
    bank = SplitFnBank(params, cfg, masks, compact, quant=quant)
    charge = None
    if simulate_server is not None:
        from repro.core.partition.latency_model import (
            batched_server_time, cnn_layer_costs, compacted_cnn_layer_costs)
        sim_costs = (compacted_cnn_layer_costs(cfg, masks)
                     if compact else cnn_layer_costs(cfg, masks))
        device_lock = threading.Lock()

        def charge(c: int, rows: int) -> None:
            dt = batched_server_time(sim_costs, c, simulate_server, rows)
            with device_lock:            # one modeled accelerator
                time.sleep(dt)

    engine = (DynamicBatcher(bank, batching, invoke_cost=charge)
              if batching else None)
    if engine is not None and on_engine is not None:
        on_engine(engine)
    warm_splits = list(resplit_candidates or ())
    if batching and split not in warm_splits:
        warm_splits.append(split)
    if warm_splits:
        # pre-jit every (candidate split x batch bucket) pair so a live
        # RESPLIT or the first concurrent burst doesn't stall its first
        # request on compilation (the edge blocks on recv meanwhile)
        bank.warm(warm_splits, _warm_input(cfg),
                  buckets=batching.resolved_buckets if batching else (1,),
                  cloud_only=True)
    shaper = LinkShaper(link, trace=trace) if link or trace else None
    _die = die if die is not None else threading.Event()
    stats_lock = threading.Lock()
    # signalled by every handler on exit so a max_clients-saturated
    # accept loop wakes the instant a slot frees instead of polling
    slot_free = threading.Event()

    def _count(key: str, n: int = 1) -> None:
        if fault_stats is None:
            return
        with stats_lock:
            fault_stats[key] = fault_stats.get(key, 0) + n

    def _handle(conn: socket.socket, rec: Dict) -> None:
        ch = (ShapedSocket(conn, link, trace=trace, shaper=shaper)
              if shaper is not None else None)
        rx, tx = _frame_io(conn, ch)
        cur_split = split
        _, cloud_fn, _ = bank.get(cur_split)
        served = 0
        # idle-client reaping: with a heartbeat interval armed, a client
        # silent for several intervals is presumed dead and its slot is
        # reclaimed (socket.timeout below)
        if fault_policy is not None and fault_policy.heartbeat_s > 0:
            conn.settimeout(3.0 * fault_policy.heartbeat_s)

        def _inject(frame: bytes) -> Optional[bytes]:
            """Server-side fault injection on one outgoing data frame."""
            if faults is None:
                return frame
            ev = faults.next_event()
            if ev is None:
                return frame
            if ev.kind == "die":
                # the cloud process is killed: stop accepting, and the
                # accept loop hard-drops every connection mid-frame
                _die.set()
                if stop is not None:
                    stop.set()
                raise ConnectionResetError("injected fault: die")
            return apply_send_fault(ev, frame, conn)

        # -- in-order response pipeline (batching mode) ---------------------
        # The handler thread keeps reading frames and submitting them to
        # the batcher; this writer drains ("ctl", bytes) and ("data",
        # seq, future|bytes) items in arrival order, so responses never
        # reorder even though batches complete asynchronously.
        resp_q: Optional[queue.Queue] = queue.Queue() if engine else None

        def _writer() -> None:
            try:
                while True:
                    item = resp_q.get()
                    if item is None:
                        return
                    if item[0] == "ctl":
                        tx(struct.pack("<Q", len(item[1])) + item[1])
                        continue
                    _, seq, val = item
                    if isinstance(val, Future):
                        val = np.asarray(val.result())   # waits: no span
                    with jax.profiler.TraceAnnotation("repro.cloud.encode"):
                        payload = (encode_tensor(val)
                                   if isinstance(val, np.ndarray) else val)
                        frame = (encode_sealed(seq, payload)
                                 if seq is not None else payload)
                    frame = _inject(frame)
                    if frame is None:
                        continue             # injected drop
                    tx(struct.pack("<Q", len(frame)) + frame)
            except (EOFError, ConnectionError, OSError):
                _count("conn_errors")
                try:
                    conn.shutdown(socket.SHUT_RDWR)      # unblock reader
                except OSError:
                    pass
            except (CancelledError, Exception):          # noqa: BLE001
                # a batch failed (or was cancelled at drain): there is no
                # payload to answer with — drop the connection so the
                # edge retries on a fresh one, and record why
                _count("writer_errors")
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

        writer = None
        if engine is not None:
            writer = threading.Thread(target=_writer, daemon=True)
            writer.start()

        def _respond_ctl(payload: bytes) -> None:
            if resp_q is not None:
                resp_q.put(("ctl", payload))
            else:
                tx(struct.pack("<Q", len(payload)) + payload)

        def _respond_data(payload: bytes, seq: Optional[int]) -> None:
            if resp_q is not None:
                resp_q.put(("data", seq, payload))
                return
            frame = (encode_sealed(seq, payload)
                     if seq is not None else payload)
            frame = _inject(frame)
            if frame is not None:
                tx(struct.pack("<Q", len(frame)) + frame)

        try:
            while max_requests is None or served < max_requests:
                (n,) = struct.unpack("<Q", rx(8))
                buf = rx(n)
                if is_heartbeat(buf):
                    # keepalive only, not a request; decode validates
                    # magic+version so a truncated frame counts as bad
                    decode_heartbeat(buf)
                    _count("heartbeats")
                    continue
                seq: Optional[int] = None
                if is_sealed(buf):
                    seq, buf = decode_sealed(buf)   # CRC-checked
                if is_hello(buf):
                    peer, _, pver = decode_hello(buf)
                    peer_caps = hello_caps(buf)
                    ok = (pver == PROTOCOL_VERSION
                          and (plan_digest is None or peer == plan_digest))
                    # capability echo: sealed frames are armed only when
                    # BOTH peers advertise CAP_CRC (legacy edges send no
                    # caps byte and keep the unsealed wire format)
                    _respond_ctl(encode_hello(
                        plan_digest or "", status=0 if ok else 1,
                        caps=CAP_CRC if peer_caps & CAP_CRC else 0))
                    if not ok:
                        return              # contract mismatch: fail fast
                    rec["claimed"] = True   # handshake is not a request
                    continue
                if is_resplit(buf):
                    want, _, pver = decode_resplit(buf)
                    ok = (pver == PROTOCOL_VERSION
                          and 0 <= want <= bank.n_layers
                          and (resplit_candidates is None
                               or want in resplit_candidates))
                    if ok:
                        cur_split = want
                        _, cloud_fn, _ = bank.get(want)
                    _respond_ctl(encode_resplit(want, status=0 if ok else 1))
                    rec["claimed"] = True   # control frame, not a request
                    continue
                if drain is not None and drain.is_set():
                    # rolling restart: stop admitting — answer DRAIN so
                    # a fleet-routed edge migrates and replays elsewhere
                    # (in-flight batched work still flushes via stop)
                    _count("drain_redirects")
                    _respond_ctl(encode_drain())
                    rec["claimed"] = True
                    continue
                with jax.profiler.TraceAnnotation("repro.cloud.decode"):
                    arr, _ = decode_any(buf)
                rows = int(np.asarray(arr).shape[0]) if arr.ndim else 1
                if (engine is not None and cur_split < bank.n_layers
                        and rows <= batching.max_batch):
                    try:
                        fut = engine.submit(cur_split, frame_lane(buf),
                                            np.asarray(arr))
                    except LaneSaturated:
                        # bounded lane overflow: shed with backpressure
                        # instead of stalling the connection — the edge
                        # redirects to another fleet member (or backs
                        # off) and replays the request
                        _count("busy_shed")
                        _respond_ctl(encode_busy("queue"))
                        rec["claimed"] = True
                        continue
                    resp_q.put(("data", seq, fut))
                else:
                    # no engine, c=N passthrough, or a frame wider than
                    # any bucket — serve it exactly like the unbatched
                    # server would (batch-1 fns accept any leading dim)
                    logits = np.asarray(
                        cloud_fn(arr) if cloud_fn is not None
                        else arr)  # c=N: edge sent the logits
                    if charge is not None and cloud_fn is not None:
                        charge(cur_split, rows)
                    _respond_data(encode_tensor(logits), seq)
                served += 1
                rec["claimed"] = True
        except FrameIntegrityError:
            # corrupted/truncated request frame: the stream can no longer
            # be trusted — close; the edge retries on a fresh connection
            _count("integrity_errors")
        except socket.timeout:
            _count("reaped_conns")          # idle past the heartbeat window
        except (EOFError, ConnectionError, OSError):
            _count("conn_errors")           # peer went away mid-stream
        except ValueError:
            _count("bad_frames")            # garbage magic / header
        finally:
            if writer is not None:
                resp_q.put(None)
                writer.join(timeout=30)
                # fail anything the dead writer left behind: a future
                # still pending is cancelled (its edge will retry), a
                # failed one is observed so it never warns unretrieved
                leaked = 0
                while True:
                    try:
                        item = resp_q.get_nowait()
                    except queue.Empty:
                        break
                    if (item is not None and item[0] == "data"
                            and isinstance(item[2], Future)):
                        fut = item[2]
                        if not fut.done():
                            fut.cancel()
                            leaked += 1
                        elif not fut.cancelled():
                            fut.exception()
                if leaked:
                    _count("abandoned_futures", leaked)
            conn.close()
            slot_free.set()     # wake a max_clients-saturated accept loop

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((host, port))
    srv.listen(16)
    srv.settimeout(0.2)
    if ready is not None:
        ready.set()
    # (thread, conn, rec) per in-flight connection; finished handlers are
    # reaped each loop turn. A connection "claims" a max_clients slot only
    # once it completes a handshake or serves a request — a stray probe,
    # a connect-and-drop, or a handshake-rejected peer can't drain a
    # bounded server before the legitimate edge connects.
    pending: List = []
    done_ok = 0
    try:
        while True:
            if (stop is not None and stop.is_set()) or _die.is_set():
                break
            live = []
            for w, c, rec in pending:
                if w.is_alive():
                    live.append((w, c, rec))
                elif rec["claimed"]:
                    done_ok += 1
            pending = live
            if max_clients is not None:
                claimed = done_ok + sum(1 for _, _, rec in pending
                                        if rec["claimed"])
                if claimed >= max_clients:
                    if not pending:
                        break               # budget served and drained
                    # block until a handler exits (slot release is
                    # immediate — no polling); the timeout only bounds
                    # how long a stop/die signal waits to be noticed
                    slot_free.wait(0.2)
                    slot_free.clear()
                    continue
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            rec = {"claimed": False}
            w = threading.Thread(target=_handle, args=(conn, rec),
                                 daemon=True)
            w.start()
            pending.append((w, conn, rec))
    finally:
        srv.close()
        if _die.is_set():
            # crash semantics: drop every connection mid-frame (the
            # fault drills' "cloud process death")
            for _, c, _ in pending:
                try:
                    c.close()
                except OSError:
                    pass
        elif stop is not None and stop.is_set():
            # graceful drain: stop READING (handlers see EOF and exit
            # their loop) but keep the write side open, so each
            # handler's writer flushes every queued / batched response
            # before the connection closes — no abandoned futures
            for _, c, _ in pending:
                try:
                    c.shutdown(socket.SHUT_RD)
                except OSError:
                    pass
        for w, _, _ in pending:
            w.join(timeout=10)
        if engine is not None:
            engine.stop()
            if batch_stats is not None:
                batch_stats.update(engine.stats())


class EdgeClient:
    """Edge side: run layers [0, split), ship features, await logits.

    ``host``/``timeout`` make a real two-machine deployment expressible
    (``repro.serving`` plumbs them from the plan's link section);
    ``plan_digest`` arms the HELLO contract handshake against the cloud.

    Two call styles:
      * ``infer(image)`` — synchronous request/response (the paper's loop);
      * ``submit(image)`` / ``collect(count)`` — pipelined: a sender thread
        runs edge compute + transmission while a receiver thread drains
        responses, so edge compute of request i+1 overlaps the network and
        cloud time of request i. Results come back in submission order.
        ``submit_group(images)`` sends several requests as ONE data frame
        of their rows (one encode, one send, one logits response), each
        request still forwarded by the batch-1 edge program.
    Do not interleave ``infer`` with outstanding ``submit``s.

    ``resplit(split)`` moves the partition point on the live connection
    (RESPLIT control frame + ack): the local edge sub-model and the cloud
    peer's ``start_layer`` swap together without reconnecting — the hook
    the adaptive split controller drives when the measured link drifts.

    Fault tolerance (``fault_policy``): every socket read carries the
    per-request deadline (a dead cloud raises ``RequestTimeout`` instead
    of blocking forever); with a policy armed, ``infer`` survives frame
    corruption (CRC), timeouts, and mid-stream disconnects by
    reconnecting — exponential backoff with deterministic jitter,
    re-HELLO, re-RESPLIT to the current split — and replaying the
    in-flight request under its sequence number. When the retry budget
    or deadline is exhausted, ``fallback="edge"`` serves the request
    locally from the bank's c=N pair (logits bit-identical to an
    all-edge deployment). Every ``infer`` result carries the uniform
    ``fault`` record (``{faults, retries, migrations, fallback}``);
    ``faults=`` attaches a client-side ``FaultInjector`` applied to
    outgoing data frames (tests/benchmarks).

    Fleet routing (``router``): with a ``FleetRouter`` attached, every
    (re)connect asks the router for the target server — rendezvous-
    hashed over this client's wire *lane* key, so same-encoding edges
    share a server and its batching lanes stay hot. Transport faults
    feed the router's health tracking (miss-count → suspect → dead) and
    the recovery loop reroutes to the next healthy member; a DRAIN
    reply migrates without spending the fault budget (rolling restart),
    a BUSY reply redirects off a saturated lane; edge-only fallback
    engages only when no routable member remains
    (``FleetExhaustedError``). ``sleep_fn`` makes the backoff sleeps
    injectable (tests run recovery in milliseconds of wall-clock).
    """

    def __init__(self, params, cfg: CNNConfig, split: int, port: int,
                 masks=None, link: Optional[LinkProfile] = None,
                 compact: bool = False, codec: Optional[str] = None,
                 pack: bool = False, host: str = "127.0.0.1",
                 timeout: float = 30.0,
                 plan_digest: Optional[str] = None,
                 trace: Optional[LinkTrace] = None,
                 fault_policy: Optional[FaultPolicy] = None,
                 faults: Optional[FaultInjector] = None,
                 router: Optional[FleetRouter] = None,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 quant=None):
        self._bank = SplitFnBank(params, cfg, masks, compact, pack,
                                 quant=quant)
        self.edge_fn, _, self._keep = self._bank.get(split)
        self.split = split
        self._plan_split = split      # the split a fresh cloud handler is at
        self.cfg = cfg
        self.codec = codec
        self._host, self._port = host, port
        self._timeout = timeout
        self._link, self._trace = link, trace
        self._digest = plan_digest
        self.policy = fault_policy
        self.faults = faults
        self._router = router
        self._avoid: Tuple[int, ...] = ()
        self._sleep = sleep_fn
        self._rng = fault_policy.make_rng() if fault_policy else None
        self._seq = 0
        self.use_crc = False
        self.last_fault = fault_record()
        self.sock: Optional[socket.socket] = None
        self.ch: Optional[ShapedSocket] = None
        self._send_q: Optional[queue.Queue] = None
        self._out_q: Optional[queue.Queue] = None
        self._outstanding = 0
        self._n_collected = 0
        self._ready: Dict[int, Dict] = {}    # dequeued-but-not-collected
        self._workers: List[threading.Thread] = []
        self._connect()

    # -- connection lifecycle ------------------------------------------------
    def _lane(self) -> str:
        """This client's wire-lane key (the ``protocol.frame_lane``
        vocabulary its data frames will carry): the fleet router hashes
        it so same-encoding edges land on one server and that server's
        batching lanes stay hot."""
        if self.codec is None and self._keep is None:
            return "raw"
        return ((self.codec or "fp32")
                + ("+packed" if self._keep is not None else ""))

    def _connect(self) -> None:
        """(Re)open the cloud connection: TCP connect, arm the read
        deadline, wrap in the shaper, HELLO (advertising the CRC
        capability), and — when the session's current split has drifted
        from the plan's (the fresh cloud handler starts there) —
        re-RESPLIT the new connection to the current split. With a
        fleet router attached the target (host, port) comes from the
        router (raising ``FleetExhaustedError`` when no member is
        routable)."""
        if self._router is not None:
            self._host, self._port = self._router.route(
                self._lane(), exclude=self._avoid)
            self._avoid = ()
        sock = socket.create_connection((self._host, self._port),
                                        timeout=self._timeout)
        # one attempt's slice of the per-request deadline is the socket
        # read timeout: a dead cloud surfaces as RequestTimeout, never a
        # forever-block, and a lost response leaves deadline budget for
        # the replays instead of consuming all of it on the first read
        sock.settimeout(self.policy.attempt_timeout_s()
                        if self.policy is not None else self._timeout)
        self.sock = sock
        self.ch = (ShapedSocket(sock, self._link, trace=self._trace)
                   if self._link or self._trace else None)
        self.use_crc = False
        if self._digest is not None:
            self._handshake(self._digest)
        if self.split != self._plan_split:
            self._resplit_on_wire(self.split)

    def _teardown(self) -> None:
        """Drop the (possibly half-dead) connection; ``_connect`` will
        rebuild it on the next attempt."""
        if self.sock is not None:
            try:
                (self.ch or self.sock).close()
            except OSError:
                pass
        self.sock = None
        self.ch = None
        self.use_crc = False

    def _handshake(self, digest: str) -> None:
        """HELLO exchange: send our plan digest, require the cloud's accept.
        Raises ``PlanMismatchError`` when the peers disagree on the
        deployment contract (or the peer cannot handshake at all). The
        HELLO advertises ``CAP_CRC``; sealed frames are armed iff the
        cloud echoes the capability (legacy clouds reply without a caps
        byte and the wire stays unsealed)."""
        hello = encode_hello(digest, caps=CAP_CRC)
        self._send(struct.pack("<Q", len(hello)) + hello)
        try:
            rx, _ = _frame_io(self.sock, self.ch)
            (n,) = struct.unpack("<Q", rx(8))
            buf = rx(n)
            peer, status, pver = decode_hello(buf)
        except (EOFError, OSError, ValueError) as e:
            self.sock.close()
            if self.policy is not None and not isinstance(e, ValueError):
                # fault-tolerant edge: a connection torn down during the
                # HELLO is transport trouble (the cloud may be dying or
                # restarting under us) — retriable, not a plan mismatch
                raise
            raise PlanMismatchError(
                f"cloud peer closed or answered garbage during the plan "
                f"handshake (legacy server without HELLO support?): {e}")
        if pver != PROTOCOL_VERSION:
            self.sock.close()
            raise PlanMismatchError(
                f"handshake protocol-version mismatch: edge speaks "
                f"v{PROTOCOL_VERSION}, cloud v{pver}")
        if status != 0 or (peer and peer != digest):
            self.sock.close()
            raise PlanMismatchError(
                f"deployment-plan mismatch: edge digest {digest!r}, "
                f"cloud digest {peer or '<unknown>'!r} — both peers must "
                f"load the same DeploymentPlan (split/compact/codec/model)")
        self.use_crc = bool(hello_caps(buf) & CAP_CRC)

    # -- framing ------------------------------------------------------------
    def _forward(self, images: Sequence[np.ndarray]) -> List:
        """The edge half of a group of requests, each through the batch-1
        program, dispatched back to back and ended by one
        ``block_until_ready``: the interval ``t_edge`` times. Returns the
        requests' device features, in order."""
        rows = sum(int(np.shape(im)[0]) for im in images)
        with jax.profiler.TraceAnnotation("repro.edge.forward",
                                          split=self.split, rows=rows):
            xs = [jnp.asarray(im) for im in images]
            if self.edge_fn is not None:
                xs = [self.edge_fn(x) for x in xs]
                jax.block_until_ready(xs)
        return xs

    def _encode_payload(self, xs: Sequence) -> bytes:
        """Frame payload of a group's features, rows in order (excluding
        the 8-byte length prefix): the prefix is transport framing, so
        reported ``tx_bytes`` stays comparable with the in-process
        executors' payload accounting."""
        x = np.concatenate([np.asarray(f) for f in xs])
        with jax.profiler.TraceAnnotation("repro.edge.encode",
                                          rows=int(x.shape[0])):
            if self.codec is None and self._keep is None:
                return encode_tensor(x)
            return encode_feature(x, codec=self.codec or "fp32",
                                  keep=self._keep)

    def _send(self, frame: bytes) -> None:
        (self.ch.sendall if self.ch else self.sock.sendall)(frame)

    def _send_payload(self, payload: bytes) -> None:
        self._send(struct.pack("<Q", len(payload)) + payload)

    def _send_request(self, seq: int, payload: bytes) -> None:
        """Ship one data frame: sealed (CRC32 + seq) when negotiated,
        with the client-side fault injector applied to the wire bytes
        (drop / corrupt / stall / tear-down) when one is attached."""
        frame = encode_sealed(seq, payload) if self.use_crc else payload
        if self.faults is not None:
            ev = self.faults.next_event()
            if ev is not None:
                maybe = apply_send_fault(ev, frame, self.sock)
                if maybe is None:
                    return              # injected drop: frame never leaves
                frame = maybe
        self._send(struct.pack("<Q", len(frame)) + frame)

    def _recv_response(self, seq: Optional[int] = None) -> np.ndarray:
        """Read one logits response. With ``seq`` set (sealed wire),
        replies are CRC-checked and matched by sequence number — a stale
        reply to a superseded attempt is discarded, corruption raises
        ``FrameIntegrityError``. A read past the deadline raises
        ``RequestTimeout``. A DRAIN/BUSY control reply (never sealed)
        raises the matching typed signal — the recovery loop migrates
        the request to another fleet member."""
        rx, _ = _frame_io(self.sock, self.ch)
        try:
            while True:
                (n,) = struct.unpack("<Q", rx(8))
                buf = rx(n)
                if is_drain(buf):
                    decode_drain(buf)       # validates magic + version
                    raise ServerDraining(
                        f"server {self._host}:{self._port} is draining "
                        f"(rolling restart)")
                if is_busy(buf):
                    reason, redirect, _ = decode_busy(buf)
                    raise ServerBusy(reason=reason, redirect=redirect)
                if is_sealed(buf):
                    rseq, buf = decode_sealed(buf)
                    if seq is not None and rseq != seq:
                        continue        # stale reply from an old attempt
                with jax.profiler.TraceAnnotation("repro.edge.decode"):
                    logits, _ = decode_tensor(buf)
                return logits
        except socket.timeout as e:
            raise RequestTimeout(
                f"no cloud response within the "
                f"{self.sock.gettimeout():.3f}s deadline") from e

    def heartbeat(self) -> None:
        """Send one keepalive frame (no reply expected) so a cloud with
        idle-client reaping armed keeps this connection alive between
        requests."""
        hb = encode_heartbeat()
        self._send(struct.pack("<Q", len(hb)) + hb)

    def warm(self, splits: Sequence[int]) -> None:
        """Pre-jit the edge half of every candidate split (batch-1 shape)
        so a live resplit doesn't stall its first request on compilation
        (the cloud warms its own halves in ``serve_cloud``)."""
        self._bank.warm(splits, _warm_input(self.cfg), edge_only=True)

    # -- live split switch --------------------------------------------------
    def resplit(self, split: int) -> None:
        """Move the split point on the live connection.

        Sends a RESPLIT control frame, requires the cloud's ack, then
        swaps the local edge sub-model — the next ``infer`` already runs
        at the new partition on the same socket. Must not be called with
        outstanding async ``submit``s (the control frame would interleave
        with in-flight tensor frames)."""
        if self._outstanding != self._n_collected:
            raise RuntimeError(
                f"resplit with {self._outstanding - self._n_collected} "
                f"outstanding pipelined request(s); collect() them first")
        self._resplit_on_wire(split)
        self.adopt_split(split)

    def _resplit_on_wire(self, split: int) -> None:
        """The raw RESPLIT exchange (frame + ack) on the live connection,
        without touching local sub-model state — shared by ``resplit``
        and the reconnect path (which re-announces the current split to
        a fresh cloud handler)."""
        self._send_payload(encode_resplit(split))
        rx, _ = _frame_io(self.sock, self.ch)
        (n,) = struct.unpack("<Q", rx(8))
        got, status, _ = decode_resplit(rx(n))
        if status != 0 or got != split:
            raise PlanMismatchError(
                f"cloud rejected resplit to c={split} (not a candidate of "
                f"its deployment plan, or outside the deployed network)")

    def adopt_split(self, split: int) -> None:
        """Swap the local edge sub-model to ``split`` without touching
        the wire — used while the cloud is unreachable (edge-only
        degradation); the next successful reconnect re-RESPLITs the
        fresh connection to this split before replaying."""
        self.edge_fn, _, self._keep = self._bank.get(split)
        self.split = split

    # -- synchronous path ---------------------------------------------------
    def _infer_edge_only(self, image: np.ndarray, rec: Dict,
                         t0: float) -> Dict:
        """Degradation-ladder bottom rung: serve the request locally from
        the bank's c=N pair — the full network jitted exactly as an
        all-edge split deploys it, so the logits are bit-identical to a
        local c=N run. No bytes cross the wire (``tx_bytes`` 0)."""
        rec["fallback"] = True
        tf0 = time.perf_counter()
        full_fn, _, _ = self._bank.get(self._bank.n_layers)
        out = full_fn(jnp.asarray(image))
        jax.block_until_ready(out)
        tf1 = time.perf_counter()
        self.last_fault = dict(rec)
        return {"logits": np.asarray(out), "t_edge": tf1 - tf0,
                "t_net_and_cloud": 0.0, "t_tx": 0.0, "tx_bytes": 0,
                "t_total_with_recovery": tf1 - t0,
                "fault": dict(rec)}

    def infer(self, image: np.ndarray) -> Dict:
        """One request/response. ``t_tx`` is the uplink observation the
        bandwidth estimator feeds on: the shaper's modeled cost of the
        feature send when the socket is shaped (wall-clock is useless
        there — the token bucket lets small frames burst through), the
        send wall-clock on a raw socket. ``t_net_and_cloud`` additionally
        includes the cloud compute and the logits downlink.

        With a ``FaultPolicy`` armed this is the recovery loop: a fault
        (timeout, disconnect, CRC failure) tears the connection down and
        the request is retried — backoff, reconnect (re-HELLO,
        re-RESPLIT), replay under the same sequence number — until the
        retry budget or the per-request deadline runs out, at which
        point the policy's fallback serves it edge-only (or re-raises).
        The ``fault`` key of the result is the uniform per-request
        record ``{faults, retries, migrations, fallback}``."""
        rec = fault_record()
        t0 = time.perf_counter()
        xs = self._forward([image])
        t1 = time.perf_counter()
        payload = self._encode_payload(xs)
        self._seq = (self._seq + 1) & 0xFFFFFFFF
        seq = self._seq
        deadline = (time.monotonic() + self.policy.request_deadline_s
                    if self.policy is not None else None)
        attempt = 0
        while True:
            try:
                if self.sock is None:
                    self._connect()     # reconnect: HELLO + re-RESPLIT
                self._send_request(seq, payload)
                t_sent = time.perf_counter()
                logits = self._recv_response(seq if self.use_crc else None)
                if self._router is not None:
                    self._router.note_ok(self._port)
                break
            except PlanMismatchError:
                raise                   # contract breakage is not transient
            except FleetExhaustedError:
                # the whole fleet is dead or draining: the bottom rung
                # (edge-only) is the only one left
                rec["faults"] += 1
                self.last_fault = dict(rec)
                if (self.policy is not None
                        and self.policy.fallback == "edge"):
                    return self._infer_edge_only(image, rec, t0)
                raise
            except ServerDraining:
                # rolling restart, not a fault: migrate to the next
                # healthy member and replay — the drained server is out
                # of the ring, so this terminates within the fleet size
                rec["migrations"] += 1
                self._teardown()
                if self._router is not None:
                    self._router.note_drain(self._port)
                    self._avoid = (self._port,)
                    continue            # immediate migration, no backoff
                exhausted = (self.policy is None
                             or attempt >= self.policy.max_retries
                             or (deadline is not None
                                 and time.monotonic() >= deadline))
                if exhausted:
                    self.last_fault = dict(rec)
                    if (self.policy is not None
                            and self.policy.fallback == "edge"):
                        return self._infer_edge_only(image, rec, t0)
                    raise
                rec["retries"] += 1
                self._sleep(self.policy.backoff_s(attempt, self._rng))
                attempt += 1
            except ServerBusy as e:
                # overload backpressure: redirect off the saturated lane
                # when the fleet has somewhere else to go, else back off
                # and retry (bounded by the normal retry budget)
                rec["migrations"] += 1
                self._teardown()
                redirect = e.redirect and self._router is not None
                if redirect:
                    self._avoid = (self._port,)
                exhausted = (self.policy is None
                             or attempt >= self.policy.max_retries
                             or (deadline is not None
                                 and time.monotonic() >= deadline))
                if exhausted:
                    self.last_fault = dict(rec)
                    if (self.policy is not None
                            and self.policy.fallback == "edge"):
                        return self._infer_edge_only(image, rec, t0)
                    raise
                rec["retries"] += 1
                if not redirect:
                    self._sleep(self.policy.backoff_s(attempt, self._rng))
                attempt += 1
            except (FrameIntegrityError, EOFError, OSError) as e:
                rec["faults"] += 1
                self._teardown()
                if self._router is not None:
                    # feed the health tracker; prefer another member on
                    # the next attempt (a lone member is still retried)
                    self._router.note_miss(self._port)
                    self._avoid = (self._port,)
                exhausted = (self.policy is None
                             or attempt >= self.policy.max_retries
                             or (deadline is not None
                                 and time.monotonic() >= deadline))
                if exhausted:
                    self.last_fault = dict(rec)
                    if (self.policy is not None
                            and self.policy.fallback == "edge"):
                        return self._infer_edge_only(image, rec, t0)
                    raise
                rec["retries"] += 1
                pause = self.policy.backoff_s(attempt, self._rng)
                if deadline is not None:
                    pause = min(pause, max(0.0,
                                           deadline - time.monotonic()))
                self._sleep(pause)
                attempt += 1
        t2 = time.perf_counter()
        self.last_fault = dict(rec)
        return {"logits": logits,
                "t_edge": t1 - t0,
                "t_net_and_cloud": t2 - t1,
                "t_tx": (self.ch.last_send_cost_s if self.ch is not None
                         else t_sent - t1),
                "tx_bytes": len(payload),
                "fault": dict(rec)}

    # -- pipelined (async) path ---------------------------------------------
    # Work moves by group: the send queue holds (request ids, images), one
    # data frame each; the receiver answers a group with one list of
    # (id, result or error) on the out queue.
    def _sender_loop(self) -> None:
        while True:
            item = self._send_q.get()
            if item is None:
                # forward the shutdown so the receiver stops only after
                # every request enqueued before close() has been answered
                self._inflight.put(None)
                break
            rids, images = item
            counts = [int(np.shape(im)[0]) for im in images]
            try:
                t0 = time.perf_counter()
                xs = self._forward(images)
                t_edge = time.perf_counter() - t0
                payload = self._encode_payload(xs)
                self._send_payload(payload)
                self._inflight.put((rids, counts, t_edge, len(payload)))
            except Exception as e:                      # noqa: BLE001
                self._inflight.put((rids, counts, e, 0))

    def _receiver_loop(self) -> None:
        while True:
            item = self._inflight.get()
            if item is None:
                break
            rids, counts, t_edge, nbytes = item
            if isinstance(t_edge, Exception):
                self._out_q.put([(rid, t_edge) for rid in rids])
                continue
            try:
                logits = self._recv_response()
            except Exception as e:                      # noqa: BLE001
                self._out_q.put([(rid, e) for rid in rids])
                continue
            # each request gets its rows of the logits and its rows'
            # share of the frame's bytes (the shares sum to the frame)
            total, off, out = sum(counts), 0, []
            for rid, n in zip(rids, counts):
                out.append((rid, {
                    "logits": logits[off:off + n], "t_edge": t_edge,
                    "tx_bytes": (nbytes * (off + n) // total
                                 - nbytes * off // total)}))
                off += n
            self._out_q.put(out)

    def submit(self, image: np.ndarray) -> int:
        """Enqueue a request; returns its id. Blocks only while the
        64-deep send queue is full (backpressure against a stalled link)."""
        return self.submit_group([image])[0]

    def submit_group(self, images: Sequence[np.ndarray]) -> List[int]:
        """Enqueue requests that travel as ONE data frame of all their
        rows, in order; returns their ids. Each result's ``t_edge`` is the
        group's forward interval and its ``tx_bytes`` the frame's bytes
        split by rows. The cloud answers the frame with one logits tensor,
        so the group should fit the server's ``max_batch`` rows (a wider
        frame bypasses its batcher)."""
        if self._send_q is None:
            self._send_q = queue.Queue(maxsize=64)
            self._inflight = queue.Queue()
            self._out_q = queue.Queue()
            self._workers = [threading.Thread(target=f, daemon=True)
                             for f in (self._sender_loop,
                                       self._receiver_loop)]
            for w in self._workers:
                w.start()
        rids = list(range(self._outstanding,
                          self._outstanding + len(images)))
        self._outstanding += len(images)
        self._send_q.put((rids, list(images)))
        return rids

    def collect(self, count: Optional[int] = None,
                timeout: float = 60.0) -> List[Dict]:
        """Block until ``count`` results (default: all outstanding) arrive;
        returns them in submission order. A request that failed raises its
        worker error (after it is consumed, so a later ``collect`` resumes
        with the requests that followed it)."""
        if count is None:
            count = self._outstanding - self._n_collected
        out: List[Dict] = []
        while len(out) < count:
            rid = self._n_collected          # next id in submission order
            if rid not in self._ready:
                self._ready.update(self._out_q.get(timeout=timeout))
                continue
            res = self._ready.pop(rid)
            self._n_collected += 1
            if isinstance(res, Exception):
                raise res
            out.append(res)
        return out

    def close(self) -> None:
        if self._send_q is not None:
            # sender forwards this sentinel to the receiver once every
            # already-queued request has been sent (no responses dropped)
            self._send_q.put(None)
            for w in self._workers:
                w.join(timeout=30)
        if self.sock is not None:
            (self.ch or self.sock).close()
