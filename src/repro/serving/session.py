"""``InferenceSession`` — one session interface over three deployment
backends, all constructed from the same ``DeploymentPlan``:

  * ``connect(plan, backend="local")`` — in-process split executor
    (``CollabRunner``): real compute, byte-accurate simulated channel,
    analytic Eq. 5 timing. The default for benchmarks and quick checks.
  * ``connect(plan, backend="socket")`` — a real TCP edge client
    (``EdgeClient``) against a cloud peer started with ``serve(plan)`` /
    ``CloudServer(plan)``. The connection opens with the HELLO handshake:
    both peers must present the same plan digest or the session fails
    fast with ``PlanMismatchError``.
  * ``connect(plan, backend="streaming")`` — the 3-stage pipelined
    in-process runtime (``StreamingCollabRunner``) for overlapped
    service of request streams.

Every backend returns the same result shape from ``infer`` /
``infer_many``::

    {"logits": np.ndarray, "t_edge": float|None, "t_upstream": float|None,
     "t_total": float|None, "tx_bytes": int|None, "e_edge_j": float|None,
     "fault": {"faults": int, "retries": int, "migrations": int,
               "fallback": bool}}

with uniform key semantics across the three backends: ``t_*`` are
seconds, ``tx_bytes`` is bytes, ``e_*`` are joules. ``t_upstream`` is
everything past the edge (network + cloud) and a ``None`` marks a
quantity the backend cannot attribute per request (e.g. per-request
wall time inside the pipelined backends). ``tx_bytes`` is the
transmitted frame *payload* — identical across backends for the same plan
(the socket path's 8-byte length prefix is framing, not payload).
``e_edge_j`` is the edge device's energy for the request, priced by the
plan's ``energy`` section (``None`` on an un-metered plan, and on the
socket backend's pipelined ``infer_many`` where the uplink time cannot
be attributed per request). ``fault`` is the uniform per-request fault
accounting (``repro.core.collab.faults.fault_record``) — all-zero on a
clean request, and the socket backend reports the faults survived, the
recovery attempts spent, and whether the request was served by the
edge-only fallback.

**Fault-tolerant plans** (``plan.faults`` set): the socket session's
``EdgeClient`` retries transient failures (reconnect + re-HELLO +
re-RESPLIT + replay by sequence number) under the policy's backoff and
deadline, and falls back to edge-only inference when the budget
exhausts. On a fallback with an adaptive plan the session reports the
outage to the controller (``note_outage`` — bandwidth collapses to ~0,
so the decision is an immediate re-split to the latest candidate,
typically c=N) and adopts the new split *locally* (``adopt_split`` —
the wire is down; the next successful reconnect re-RESPLITs to it);
once requests flow again, healthy uplink observations pull the
estimate back up and the controller re-splits toward offloading.

**Adaptive plans** (``plan.adaptive`` set): the ``local`` and ``socket``
sessions close the control loop per request — each ``infer`` feeds its
uplink observation to an ``AdaptiveSplitController``, and when the
measured link has drifted past the hysteresis margin the session switches
the split in place (``CollabRunner.set_split`` locally; the RESPLIT
control frame on the live socket). ``session.split`` is the current
partition and ``session.switches`` the decision log. Pass a ``LinkTrace``
via ``connect(plan, trace=...)`` (and ``serve(plan, trace=...)``) to
replay a time-varying link.

**Fleet-routed plans** (``plan.routing`` set): the socket session builds
a ``FleetRouter`` over the plan's fleet member ports and the edge client
routes by its wire-lane key (rendezvous hashing — one lane stays hot on
one server). ``CloudFleet`` starts one ``CloudServer`` per member port
and drives the chaos drills: ``kill`` (crash), ``drain`` (rolling
restart — victims answer new requests with the DRAIN frame and edges
migrate with zero failed requests), ``restart`` (heal back into the
ring). When every member is gone the edge degrades to the bit-identical
edge-only fallback, exactly as for a single-server cloud death.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.collab.adaptive import (AdaptiveSplitController,
                                        SplitSwitch)
from repro.core.collab.batching import DynamicBatcher, bucket_for
from repro.core.collab.channel import FaultInjector
from repro.core.collab.cluster import FleetRouter
from repro.core.collab.faults import fault_record
from repro.core.collab.protocol import PlanMismatchError  # re-export  # noqa: F401
from repro.core.collab.runtime import (CollabRunner, EdgeClient,
                                       serve_cloud)
from repro.core.collab.streaming import StreamingCollabRunner, StreamReport
from repro.core.partition.profiles import LinkTrace
from repro.serving.plan import DeploymentPlan

BACKENDS = ("local", "socket", "streaming")


def _controller_for(plan: DeploymentPlan) -> Optional[AdaptiveSplitController]:
    if plan.adaptive is None:
        return None
    return AdaptiveSplitController.for_deployment(
        plan.cfg, plan.adaptive, plan.split, plan.profile, masks=plan.masks,
        compact=plan.compact, codec=plan.codec, pack=plan.pack,
        energy=plan.energy)


def _row_groups(images: Sequence[np.ndarray], max_rows: int
                ) -> List[Tuple[List[np.ndarray], int]]:
    """Consecutive requests, in order, as (group, rows) with at most
    ``max_rows`` rows a group (a multi-row request counts its rows); a
    request wider than ``max_rows`` forms a group alone."""
    groups: List[List[np.ndarray]] = []
    rows: List[int] = []
    for img in images:
        n = int(np.shape(img)[0])
        if not groups or rows[-1] + n > max_rows:
            groups.append([])
            rows.append(0)
        groups[-1].append(img)
        rows[-1] += n
    return list(zip(groups, rows))


def _result(logits, t_edge: Optional[float], t_upstream: Optional[float],
            tx_bytes: Optional[int],
            e_edge_j: Optional[float] = None,
            fault: Optional[Dict] = None) -> Dict:
    """The one result shape every backend returns: ``t_*`` seconds,
    ``tx_bytes`` bytes, ``e_edge_j`` joules (None = unattributable or
    un-metered), ``fault`` the uniform ``{faults, retries, migrations,
    fallback}`` accounting (all-zero when the backend reports none)."""
    total = (None if t_edge is None or t_upstream is None
             else t_edge + t_upstream)
    return {"logits": np.asarray(logits), "t_edge": t_edge,
            "t_upstream": t_upstream, "t_total": total,
            "tx_bytes": tx_bytes, "e_edge_j": e_edge_j,
            "fault": dict(fault) if fault else fault_record()}


class InferenceSession:
    """Base session: one deployed plan, uniform request interface.

    ``split`` is the *current* partition point (it moves under an
    adaptive plan); ``switches`` logs every ``SplitSwitch`` the adaptive
    controller executed on this session.
    """

    backend: str = "?"

    def __init__(self, plan: DeploymentPlan):
        self.plan = plan
        self.split: int = plan.split
        self.switches: List[SplitSwitch] = []

    def infer(self, image: np.ndarray) -> Dict:
        """Serve one request (image ``(B, H, W, C)`` float32); returns
        the uniform result dict (``t_*`` seconds, ``tx_bytes`` bytes,
        ``e_edge_j`` joules)."""
        raise NotImplementedError

    def infer_many(self, images: Sequence[np.ndarray]) -> List[Dict]:
        """Serve a batch of requests; pipelined backends overlap them."""
        return [self.infer(img) for img in images]

    def close(self) -> None:
        """Release the backend's resources (sockets, worker threads);
        in-process backends need no teardown."""
        pass

    def __enter__(self) -> "InferenceSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class LocalSession(InferenceSession):
    """In-process split executor. ``t_edge``/``t_upstream`` come from the
    analytic hardware profile when ``simulate_compute`` (the default —
    this container is not an i7/3090 pair); the channel term is always
    charged per transmitted byte. A ``trace`` replays a time-varying
    link on the simulated channel; with an adaptive plan the session
    re-splits itself as the charged per-send costs reveal the drift."""

    backend = "local"

    def __init__(self, plan: DeploymentPlan, *,
                 realtime_channel: bool = False,
                 simulate_compute: bool = True,
                 trace: Optional[LinkTrace] = None,
                 faults: Optional[FaultInjector] = None):
        super().__init__(plan)
        self._runner = CollabRunner(
            plan.params, plan.cfg, plan.split, plan.profile,
            masks=plan.masks, realtime_channel=realtime_channel,
            simulate_compute=simulate_compute, compact=plan.compact,
            codec=plan.codec, pack=plan.pack, trace=trace,
            energy=plan.energy.profile if plan.energy else None,
            faults=faults, quant=plan.quant)
        self._controller = _controller_for(plan)
        if self._controller is not None:
            # pre-jit every candidate so a switch doesn't stall a request
            self._runner.warm(plan.adaptive.candidates)

    def infer(self, image: np.ndarray) -> Dict:
        """One in-process request: device/server terms from the analytic
        profile (seconds), channel charged per byte, ``e_edge_j`` priced
        by the plan's energy section; feeds the adaptive controller."""
        res = self._runner.infer(image)
        t = res["timing"]
        if self._controller is not None:
            sw = self._controller.step(t.tx_bytes, t.t_tx, t.e_edge_j)
            if sw is not None:
                self._runner.set_split(sw.new_split)
                self.split = sw.new_split
                self.switches.append(sw)
        return _result(res["logits"], t.t_device, t.t_tx + t.t_server,
                       t.tx_bytes, t.e_edge_j, fault=res.get("fault"))

    def infer_many(self, images: Sequence[np.ndarray]) -> List[Dict]:
        """Batched fast path when the plan carries a ``batching`` section
        (and no adaptive controller needs per-request observations):
        requests are fused up to ``max_batch`` ROWS at a time through ONE
        edge call and ONE bucketed cloud call
        (``CollabRunner.infer_batch``), with logits bit-identical to the
        sequential loop. A single request wider than ``max_batch`` rows
        falls back to the sequential path (which accepts any batch)."""
        if self.plan.batching is None or self._controller is not None:
            return super().infer_many(images)
        mb = self.plan.batching.max_batch
        buckets = self.plan.batching.resolved_buckets
        out: List[Dict] = []
        for group, rows in _row_groups(images, mb):
            if rows > mb:                # one request wider than any bucket
                out.append(self.infer(group[0]))
                continue
            for r in self._runner.infer_batch(
                    group, bucket=bucket_for(rows, buckets)):
                t = r["timing"]
                out.append(_result(r["logits"], t.t_device,
                                   t.t_tx + t.t_server, t.tx_bytes,
                                   t.e_edge_j, fault=r.get("fault")))
        return out


class SocketSession(InferenceSession):
    """Edge side of the real-socket deployment. Requires a cloud peer
    (``serve``/``CloudServer``) listening at the plan's link endpoint;
    ``verify=True`` (default) runs the HELLO digest handshake.

    With an adaptive plan, each synchronous ``infer`` feeds the measured
    send wall-clock to the controller and executes any decided switch via
    the RESPLIT frame — same connection, no re-handshake. ``resplit``
    forces a switch manually. A ``trace`` shapes the edge's uplink
    against a time-varying link (pair it with ``serve(plan, trace=...)``
    for the downlink).

    With a fleet-routed plan (``plan.routing`` set) the session builds a
    ``FleetRouter`` over the fleet member ports (or adopts a shared one
    passed as ``router``) and the client picks its server per connect by
    lane key; ``session.router`` exposes the health/reroute stats.
    ``sleep_fn`` replaces the retry-backoff sleep (tests inject a no-op
    to run failover drills in milliseconds)."""

    backend = "socket"

    def __init__(self, plan: DeploymentPlan, *, verify: bool = True,
                 host: Optional[str] = None, port: Optional[int] = None,
                 trace: Optional[LinkTrace] = None,
                 faults: Optional[FaultInjector] = None,
                 router: Optional[FleetRouter] = None,
                 sleep_fn=None):
        super().__init__(plan)
        if router is None and plan.routing is not None:
            router = FleetRouter(plan.routing, host=host or plan.host)
        #: the fleet router steering this session's connects (None on a
        #: single-server plan) — shared health state if passed in
        self.router = router
        self._client = EdgeClient(
            plan.params, plan.cfg, plan.split, port or plan.port,
            masks=plan.masks,
            link=plan.profile.link if plan.shape_link else None,
            compact=plan.compact, codec=plan.codec, pack=plan.pack,
            host=host or plan.host, timeout=plan.connect_timeout_s,
            plan_digest=plan.digest if verify else None, trace=trace,
            fault_policy=plan.faults, faults=faults, router=router,
            quant=plan.quant,
            **({"sleep_fn": sleep_fn} if sleep_fn is not None else {}))
        self._controller = _controller_for(plan)
        if self._controller is not None:
            # pre-jit the edge half of every candidate (the cloud peer
            # warms its own halves when it arms RESPLIT)
            self._client.warm(plan.adaptive.candidates)
        if plan.faults is not None and plan.faults.fallback == "edge":
            # pre-jit the c=N pair so the first edge-only fallback does
            # not pay an XLA trace in the middle of an outage
            self._client.warm([len(plan.cfg.layers)])

    def resplit(self, split: int) -> None:
        """Move the partition on the live connection (RESPLIT + ack).
        With an adaptive plan the controller adopts the override and its
        dwell window restarts (it won't overrule it on the next infer)."""
        self._client.resplit(split)
        self.split = split
        if self._controller is not None:
            self._controller.note_external_switch(split)

    def _energy(self, res: Dict) -> Optional[float]:
        """Price one synchronous request's edge joules from its measured
        breakdown: edge compute wall-clock, the channel's modeled uplink
        cost, and the remaining wait (cloud + downlink)."""
        if self.plan.energy is None:
            return None
        t_wait = max(res["t_net_and_cloud"] - res["t_tx"], 0.0)
        return self.plan.energy.profile.request_energy(
            res["t_edge"], res["t_tx"], t_wait,
            rtt_s=self.plan.profile.link.rtt_s)

    def infer(self, image: np.ndarray) -> Dict:
        """One synchronous request/response on the live socket; measured
        wall-clock timing (seconds), modeled uplink cost as ``t_tx``,
        ``e_edge_j`` joules when metered; feeds the adaptive controller
        and executes any decided RESPLIT."""
        res = self._client.infer(image)
        e = self._energy(res)
        rec = res.get("fault")
        if self._controller is not None:
            if rec and rec["fallback"]:
                # outage: the cloud is unreachable, so the switch (if
                # any) is adopted locally — the client re-RESPLITs the
                # wire on its next successful reconnect
                sw = self._controller.note_outage()
                if sw is not None:
                    self._client.adopt_split(sw.new_split)
                    self.split = sw.new_split
                    self.switches.append(sw)
            else:
                sw = self._controller.step(res["tx_bytes"], res["t_tx"], e)
                if sw is None and rec and rec["migrations"]:
                    # fleet backpressure: let the controller answer the
                    # congestion signal without waiting out the dwell
                    sw = self._controller.note_congestion()
                if sw is not None:
                    self._client.resplit(sw.new_split)
                    self.split = sw.new_split
                    self.switches.append(sw)
        return _result(res["logits"], res["t_edge"],
                       res["t_net_and_cloud"], res["tx_bytes"], e,
                       fault=rec)

    def infer_many(self, images: Sequence[np.ndarray]) -> List[Dict]:
        """Pipelined submit/collect: edge compute of frame i+1 overlaps
        network + cloud time of frame i. Results in submission order.

        On a plan with a ``batching`` section the requests travel in
        groups: consecutive requests, up to ``max_batch`` rows, share ONE
        data frame (a request wider than ``max_batch`` goes alone), so the
        socket, codec and thread hand-offs are paid once a group. Each
        request still runs the batch-1 edge program, and the int8 codec
        quantizes each row on its own, so every answer is bit-identical
        to its own frame's. A grouped result's ``t_edge`` is its group's
        forward interval and its ``tx_bytes`` the frame's bytes split by
        rows. Without ``batching`` every request is its own frame: that
        server would run a multi-row frame as one batch-k cloud call.

        With an adaptive plan this falls back to the sequential per-request
        loop: the control loop needs a per-request uplink observation and a
        quiesced connection to switch on, neither of which the async
        pipeline provides (a RESPLIT cannot interleave with in-flight
        frames)."""
        if self._controller is not None:
            return [self.infer(img) for img in images]
        if self.plan.batching is None:
            groups = [[img] for img in images]
        else:
            groups = [g for g, _ in _row_groups(images,
                                                self.plan.batching.max_batch)]
        for group in groups:
            self._client.submit_group(group)
        out = self._client.collect(len(images))
        return [_result(r["logits"], r["t_edge"], None, r["tx_bytes"],
                        fault=r.get("fault"))
                for r in out]

    def close(self) -> None:
        """Drain any pipelined requests and close the TCP connection."""
        self._client.close()


class StreamingSession(InferenceSession):
    """3-stage pipelined in-process backend (edge ∥ link ∥ cloud).
    ``infer_many`` is the native call; the full ``StreamReport`` of the
    last run (occupancy, throughput, wire bytes) is on ``last_report``."""

    backend = "streaming"

    def __init__(self, plan: DeploymentPlan, *, queue_depth: int = 4,
                 microbatch: int = 1, realtime_channel: bool = True,
                 trace: Optional[LinkTrace] = None):
        super().__init__(plan)
        self._runner = StreamingCollabRunner(
            plan.params, plan.cfg, plan.split, plan.profile,
            masks=plan.masks, compact=plan.compact, codec=plan.codec,
            pack=plan.pack, queue_depth=queue_depth, microbatch=microbatch,
            realtime_channel=realtime_channel, trace=trace,
            quant=plan.quant)
        self.last_report: Optional[StreamReport] = None

    def infer(self, image: np.ndarray) -> Dict:
        """Serve one request through the pipeline (prefer ``infer_many``
        — a single request cannot overlap anything)."""
        return self.infer_many([image])[0]

    def infer_many(self, images: Sequence[np.ndarray]) -> List[Dict]:
        rep = self._runner.run(list(images))
        self.last_report = rep
        energy = self.plan.energy.profile if self.plan.energy else None
        n = max(len(rep.results), 1)
        # per-request stage attribution: measured busy wall-clock of the
        # edge/cloud stages amortized over the stream, plus the channel's
        # *modeled* per-request uplink cost (the pipelined wall-clock of
        # an individual request is not observable, which is why t_* stay
        # None below — but the energy integral over the stream is)
        t_edge_amort = rep.stages["edge"].busy_s / n
        t_cloud_amort = rep.stages["cloud"].busy_s / n
        out = []
        for r in rep.results:
            # a micro-batched frame pays ONE RTT shared by its requests,
            # and t_tx_model above is that frame's cost split evenly —
            # so the RTT peeled off in the energy formula must be split
            # the same way or multi-request frames would zero their
            # radio-active TX time
            e = (energy.request_energy(
                    t_edge_amort, r["t_tx_model"], t_cloud_amort,
                    rtt_s=self.plan.profile.link.rtt_s / r["frame_n"])
                 if energy is not None else None)
            out.append(_result(r["logits"], None, None,
                               int(r["tx_bytes"]), e))
        return out


def connect(plan: DeploymentPlan, backend: str = "local",
            **opts) -> InferenceSession:
    """Open an ``InferenceSession`` on ``plan`` with the chosen backend.
    All backends serve the same contract and return the same result
    shape; extra ``opts`` are backend-specific (see each session class).
    """
    if backend == "local":
        return LocalSession(plan, **opts)
    if backend == "socket":
        return SocketSession(plan, **opts)
    if backend == "streaming":
        return StreamingSession(plan, **opts)
    raise ValueError(f"unknown backend {backend!r} (use {BACKENDS})")


def serve(plan: DeploymentPlan, *, port: Optional[int] = None,
          host: Optional[str] = None, max_requests: Optional[int] = None,
          max_clients: Optional[int] = 1,
          ready: Optional[threading.Event] = None,
          stop: Optional[threading.Event] = None,
          verify: bool = True,
          trace: Optional[LinkTrace] = None,
          batch_stats: Optional[Dict] = None,
          simulate_server=None,
          faults: Optional[FaultInjector] = None,
          fault_stats: Optional[Dict] = None,
          die: Optional[threading.Event] = None,
          drain: Optional[threading.Event] = None,
          on_engine: Optional[Callable[[DynamicBatcher], None]] = None,
          ) -> None:
    """Cloud-side entry point: serve ``plan`` on its link endpoint
    (blocking). ``max_clients=None`` + a ``stop`` event serves many edges
    until told to quit; ``verify`` arms the HELLO digest check. An
    adaptive plan arms the RESPLIT path, restricted to the plan's
    candidate splits; a non-adaptive plan still answers RESPLIT for any
    split valid on the deployed network (manual ``resplit``). A plan with
    a ``batching`` section serves through the cross-client dynamic
    batching engine; pass a dict as ``batch_stats`` to receive its
    per-lane accounting (fill rate, padding waste) on shutdown, or
    ``on_engine`` to be handed the engine itself before the server
    listens (``CloudServer.lane_stats`` reads it live).
    ``simulate_server`` (a ``ComputeProfile``) additionally charges each
    cloud invocation its analytic device time on that hardware,
    serialized server-wide (see ``serve_cloud``) — the benchmark knob for
    measuring the engine against the paper's 3090 on this container.

    A plan with a ``faults`` section arms the server's recovery side:
    sealed (CRC + sequence) frames are negotiated per connection via the
    HELLO caps byte, clients silent for ``3 * heartbeat_s`` are reaped,
    and a ``stop`` becomes a graceful drain (in-flight batched requests
    flush before the listener exits). ``faults`` (a ``FaultInjector``)
    injects the schedule into the server's response path; ``fault_stats``
    (a dict) receives classified error counts on shutdown; ``die`` is
    the crash switch — setting it kills every connection without drain
    (what ``CloudServer.kill`` uses to simulate cloud death); ``drain``
    is the rolling-restart switch — while set, new data requests are
    answered with the versioned DRAIN control frame (fleet-routed edges
    migrate to another member, zero failed requests) while handshakes
    and in-flight work still complete (what ``CloudServer.drain``
    sets)."""
    serve_cloud(plan.params, plan.cfg, plan.split, port or plan.port,
                masks=plan.masks,
                link=plan.profile.link if plan.shape_link else None,
                max_requests=max_requests, ready=ready,
                compact=plan.compact, host=host or plan.host,
                max_clients=max_clients, stop=stop,
                plan_digest=plan.digest if verify else None,
                resplit_candidates=(plan.adaptive.candidates
                                    if plan.adaptive else None),
                trace=trace, batching=plan.batching,
                batch_stats=batch_stats, simulate_server=simulate_server,
                fault_policy=plan.faults, faults=faults,
                fault_stats=fault_stats, die=die, drain=drain,
                quant=plan.quant, on_engine=on_engine)


class CloudServer:
    """Background cloud peer for a plan (thread wrapper around ``serve``).

    >>> with CloudServer(plan, max_clients=None) as srv:
    ...     sess = connect(plan, backend="socket")
    """

    def __init__(self, plan: DeploymentPlan, *,
                 port: Optional[int] = None, host: Optional[str] = None,
                 max_requests: Optional[int] = None,
                 max_clients: Optional[int] = None, verify: bool = True,
                 trace: Optional[LinkTrace] = None,
                 simulate_server=None,
                 faults: Optional[FaultInjector] = None):
        self.plan = plan
        #: per-lane dynamic-batching accounting (filled on shutdown when
        #: the plan carries a ``batching`` section)
        self.batch_stats: Dict = {}
        #: classified server-side error counts (filled on shutdown)
        self.fault_stats: Dict = {}
        #: the batching engine, handed over by the serve thread before
        #: it listens (None when the plan does not batch)
        self._engine: Optional[DynamicBatcher] = None
        self._stop = threading.Event()
        self._die = threading.Event()
        self._drain = threading.Event()
        ready = threading.Event()
        failed: List[Exception] = []

        def _serve() -> None:
            try:
                serve(plan, port=port, host=host, max_requests=max_requests,
                      max_clients=max_clients, ready=ready,
                      stop=self._stop, verify=verify, trace=trace,
                      batch_stats=self.batch_stats,
                      simulate_server=simulate_server, faults=faults,
                      fault_stats=self.fault_stats, die=self._die,
                      drain=self._drain, on_engine=self._adopt_engine)
            except Exception as e:                   # noqa: BLE001
                failed.append(e)         # re-raised below, as the cause

        self._thread = threading.Thread(target=_serve, daemon=True)
        self._thread.start()
        # the serve thread compiles every (split, bucket) executable
        # before it listens — minutes on a cold cache — so the wait
        # lasts as long as the thread does, and ends only in a listener
        # or in the thread's own error
        while not ready.wait(0.1):
            if not self._thread.is_alive():
                raise RuntimeError(
                    "cloud server exited before listening"
                ) from (failed[0] if failed else None)

    def _adopt_engine(self, engine: DynamicBatcher) -> None:
        self._engine = engine

    def lane_stats(self) -> Dict[str, Dict]:
        """The batching engine's per-lane counters while the server runs
        (``DynamicBatcher.stats``: rows, frames, batches, ``busy_s``,
        ``queue_wait_s``, live queue depth); ``{}`` when the plan does not
        batch. ``batch_stats`` holds the same records only after
        ``stop``."""
        return self._engine.stats() if self._engine is not None else {}

    def stop(self, timeout: float = 10.0) -> None:
        """Signal the serve loop to quit and join its thread (seconds):
        a *graceful drain* — in-flight batched requests flush before the
        listener exits; fills ``batch_stats`` when the plan batches."""
        self._stop.set()
        self._thread.join(timeout)

    def drain(self) -> None:
        """Start a rolling-restart drain: stop admitting new data
        requests — each gets the DRAIN control frame so fleet-routed
        edges migrate to another member — while handshakes and in-flight
        batched work still complete. Returns immediately; call ``stop``
        once the edges have moved (``CloudFleet.restart`` sequences
        this)."""
        self._drain.set()

    @property
    def draining(self) -> bool:
        """True once a rolling-restart drain has been started."""
        return self._drain.is_set()

    def kill(self, timeout: float = 10.0) -> None:
        """Simulated cloud death: hard-close every connection (no drain,
        no goodbye — clients see a reset mid-stream) and join the serve
        thread. The fault-injection benchmark's 'cloud process dies'
        event; a fault-tolerant edge recovers by reconnecting to a fresh
        server, everyone else gets a ``ConnectionError``."""
        self._die.set()
        self._stop.set()
        self._thread.join(timeout)

    def join(self, timeout: float = 30.0) -> None:
        """Wait for a bounded server (``max_clients`` set) to drain."""
        self._thread.join(timeout)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def __enter__(self) -> "CloudServer":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


class CloudFleet:
    """The high-availability cloud tier: one background ``CloudServer``
    per fleet member port in ``plan.routing``, plus the chaos controls
    the failover drills drive — ``kill`` (crash a member), ``drain``
    (rolling restart: the member answers new requests with DRAIN and
    fleet-routed edges migrate with zero failed requests), ``restart``
    (heal a member back into the ring).

    >>> with CloudFleet(plan) as fleet:
    ...     sess = connect(plan, backend="socket")   # routes by lane
    ...     fleet.kill(plan.routing.ports[0])        # edges re-route
    """

    def __init__(self, plan: DeploymentPlan, *, verify: bool = True,
                 max_clients: Optional[int] = None,
                 simulate_server=None):
        if plan.routing is None or not plan.routing.ports:
            raise ValueError(
                "CloudFleet needs a plan with a routing section "
                "(fleet member ports)")
        self.plan = plan
        self._verify = verify
        self._max_clients = max_clients
        self._simulate_server = simulate_server
        self._lock = threading.Lock()
        self._servers: Dict[int, CloudServer] = {}
        for p in plan.routing.ports:
            self._servers[p] = self._spawn(p)

    def _spawn(self, port: int) -> CloudServer:
        return CloudServer(
            self.plan, port=port, max_clients=self._max_clients,
            verify=self._verify, simulate_server=self._simulate_server)

    @property
    def ports(self) -> tuple:
        """The fleet member ports (the plan's routing section)."""
        return self.plan.routing.ports

    def server(self, port: int) -> CloudServer:
        """The current ``CloudServer`` for one member port."""
        with self._lock:
            return self._servers[port]

    def kill(self, port: int, timeout: float = 10.0) -> None:
        """Crash one member: hard-close its connections (no drain, no
        goodbye). Fleet-routed edges see the reset, mark the member
        dead, and re-route the replayed request to the next healthy
        server."""
        self.server(port).kill(timeout)

    def drain(self, port: int) -> None:
        """Start a rolling-restart drain on one member (see
        ``CloudServer.drain``); returns immediately while edges
        migrate."""
        self.server(port).drain()

    def stop(self, port: int, timeout: float = 10.0) -> None:
        """Gracefully stop one member (in-flight work flushes)."""
        self.server(port).stop(timeout)

    def restart(self, port: int, timeout: float = 10.0) -> CloudServer:
        """Bring a killed/drained member back: stop whatever is left on
        the port and start a fresh ``CloudServer`` there. The routers'
        dead-member probe (``retry_dead_s``) heals it back into the
        ring; a drill can also call ``router.revive(port)`` directly."""
        old = self.server(port)
        if old.alive:
            old.stop(timeout)
        srv = self._spawn(port)
        with self._lock:
            self._servers[port] = srv
        return srv

    def stop_all(self, timeout: float = 10.0) -> None:
        """Gracefully stop every member of the fleet."""
        with self._lock:
            servers = list(self._servers.values())
        for srv in servers:
            srv.stop(timeout)

    def __enter__(self) -> "CloudFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.stop_all()
