"""High-availability cloud tier: fleet routing (rendezvous hashing),
health-checked failover, graceful drain/rolling restart, overload
backpressure (BUSY), and the edge-only bottom rung when the whole
fleet is gone.

The e2e drills run a real ``CloudFleet`` (one ``CloudServer`` per fleet
member port) against fleet-routed ``SocketSession``s with a no-op
``sleep_fn``, so every recovery path executes in milliseconds of
wall-clock; all logits assertions are bit-exact against the same
deployed network.
"""
from __future__ import annotations

import socket
import struct
import threading
import time

import jax
import numpy as np
import pytest

from repro import serving
from repro.core.collab.batching import (BatchingPolicy, DynamicBatcher,
                                        LaneSaturated)
from repro.core.collab.cluster import (FleetExhaustedError, FleetRouter,
                                       RoutingPolicy, _rendezvous_score)
from repro.core.collab.protocol import (decode_busy, decode_drain,
                                        decode_tensor, encode_busy,
                                        encode_drain, encode_feature,
                                        encode_heartbeat, is_busy, is_drain)
from repro.core.collab.runtime import SplitFnBank
from repro.core.fleet import ChaosEvent, FleetScenario, simulate_fleet
from repro.core.partition.profiles import ComputeProfile, FaultEvent
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.models.cnn import (cnn_apply, init_cnn_params, prunable_layers,
                              tiny_cnn_config)

SPLIT = 6
LANE = "fp32"        # the wire lane of a compact/fp32/unpacked plan


@pytest.fixture(scope="module")
def plan_setup():
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    masks = cnn_masks_from_ratios(
        params, cfg, {i: 0.5 for i in prunable_layers(cfg)})
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3)),
                   np.float32)
    want = np.asarray(cnn_apply(params, cfg, x, masks=masks))
    return cfg, params, masks, x, want


def make_plan(plan_setup, port, **kw):
    cfg, params, masks, _, _ = plan_setup
    kw.setdefault("split", SPLIT)
    kw.setdefault("masks", masks)
    kw.setdefault("compact", True)
    kw.setdefault("codec", "fp32")
    kw.setdefault("shape_link", False)
    return serving.DeploymentPlan.from_args(params, cfg, port=port, **kw)


def fast_policy(**kw):
    """Milliseconds-scale recovery knobs so drills never idle."""
    kw.setdefault("max_retries", 3)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_max_s", 0.05)
    kw.setdefault("backoff_jitter", 0.0)
    kw.setdefault("request_deadline_s", 5.0)
    kw.setdefault("fallback", "edge")
    return serving.FaultPolicy(**kw)


def winner(ports, key=LANE, exclude=()):
    """The rendezvous pick for ``key`` among ``ports`` (test oracle)."""
    cands = [p for p in ports if p not in exclude] or list(ports)
    return max(cands, key=lambda p: (_rendezvous_score(key, p), p))


# ---------------------------------------------------------------------------
# RoutingPolicy (pure data)
# ---------------------------------------------------------------------------
def test_routing_policy_roundtrip_and_validation():
    p = RoutingPolicy(ports=(29540, 29541, 29542), suspect_after_count=2,
                      dead_after_count=4, retry_dead_s=1.5)
    assert RoutingPolicy.from_json(p.to_json()) == p
    with pytest.raises(ValueError, match="duplicate"):
        RoutingPolicy(ports=(1, 1))
    with pytest.raises(ValueError, match="suspect_after_count"):
        RoutingPolicy(ports=(1,), suspect_after_count=0)
    with pytest.raises(ValueError, match="dead_after_count"):
        RoutingPolicy(ports=(1,), suspect_after_count=3, dead_after_count=2)
    with pytest.raises(ValueError, match="retry_dead_s"):
        RoutingPolicy(ports=(1,), retry_dead_s=0)


def test_plan_routing_section_folds_into_digest_only_when_set(
        plan_setup, tmp_path):
    base = make_plan(plan_setup, 29540)
    assert "routing" not in base.contract()      # only-when-set fold
    rp = RoutingPolicy(ports=(29540, 29541, 29542))
    routed = make_plan(plan_setup, 29540, routing=rp)
    assert routed.contract()["routing"] == rp.to_json()
    assert base.digest != routed.digest
    path = routed.save(str(tmp_path / "deploy"))
    reloaded = serving.DeploymentPlan.load(path)
    assert reloaded.routing == rp
    assert reloaded.digest == routed.digest


# ---------------------------------------------------------------------------
# FleetRouter (unit, fake clock)
# ---------------------------------------------------------------------------
def test_rendezvous_routing_is_stable_and_minimally_disruptive():
    ports = (29540, 29541, 29542)
    t = [0.0]
    r = FleetRouter(RoutingPolicy(ports=ports), clock=lambda: t[0])
    host, p1 = r.route(LANE)
    assert host == "127.0.0.1" and p1 == winner(ports)
    assert r.route(LANE)[1] == p1            # same key -> same member
    # losing a NON-winning member must not remap the lane (the
    # rendezvous property a mod-N ring does not have)
    loser = next(q for q in ports if q != p1)
    r.note_miss(loser)
    r.note_miss(loser)                       # dead at the default ladder
    assert r.route(LANE)[1] == p1
    assert set(r.healthy_ports()) == set(ports) - {loser}


def test_route_exclusion_is_a_preference_not_a_filter():
    ports = (29540, 29541, 29542)
    r = FleetRouter(RoutingPolicy(ports=ports))
    p1 = r.route(LANE)[1]
    p2 = r.route(LANE, exclude=(p1,))[1]
    assert p2 != p1 and p2 == winner(ports, exclude=(p1,))
    assert r.stats()["reroutes_count"] == 1
    # excluding everything still hands out a member: a lone server is
    # retried, never silently dropped (and that is not a reroute)
    p3 = r.route(LANE, exclude=ports)[1]
    assert p3 in ports
    assert r.stats()["reroutes_count"] == 1


def test_health_ladder_miss_suspect_dead_and_timed_reprobe():
    t = [0.0]
    r = FleetRouter(RoutingPolicy(ports=(1, 2), suspect_after_count=1,
                                  dead_after_count=2, retry_dead_s=5.0),
                    clock=lambda: t[0])
    assert r.state(1) == "healthy"
    assert r.note_miss(1) == "suspect"
    assert 1 in r.healthy_ports()            # suspect is still routable
    assert r.note_miss(1) == "dead"
    assert r.healthy_ports() == (2,)
    with pytest.raises(FleetExhaustedError):
        r.note_miss(2), r.note_miss(2)
        r.route(LANE)
    t[0] = 4.9
    assert r.healthy_ports() == ()
    t[0] = 5.0                               # retry window: dead -> probe
    assert set(r.healthy_ports()) == {1, 2}
    r.note_ok(1)                             # a probe success heals it
    assert r.state(1) == "healthy"
    # drain is sticky: not routable, immune to note_ok, until revive
    r.note_drain(1)
    r.note_ok(1)
    assert r.state(1) == "draining" and 1 not in r.healthy_ports()
    r.revive(1)
    assert r.state(1) == "healthy"
    st = r.stats()["servers"]
    assert st[2]["state"] == "dead" and st[2]["miss_count"] == 2


# ---------------------------------------------------------------------------
# DRAIN / BUSY control frames
# ---------------------------------------------------------------------------
def test_drain_and_busy_frame_roundtrips():
    d = encode_drain()
    assert is_drain(d) and not is_busy(d)
    assert decode_drain(d) == (0, 1)
    b = encode_busy("queue", redirect=False)
    assert is_busy(b) and not is_drain(b)
    assert decode_busy(b) == ("queue", False, 1)
    assert decode_busy(encode_busy("queue"))[1] is True
    with pytest.raises(ValueError, match="BUSY reason"):
        encode_busy("martians")
    with pytest.raises(ValueError, match="magic"):
        decode_drain(b)
    with pytest.raises(ValueError, match="magic"):
        decode_busy(d)
    assert not is_drain(encode_heartbeat())
    assert not is_busy(b"")


# ---------------------------------------------------------------------------
# bounded lanes (unit)
# ---------------------------------------------------------------------------
def test_batching_policy_max_queue_roundtrip_and_validation():
    p = BatchingPolicy(max_batch=4, max_queue=2)
    assert p.to_json()["max_queue"] == 2
    assert BatchingPolicy.from_json(p.to_json()) == p
    # unbounded lanes serialize WITHOUT the key: pre-HA plan digests
    # must stay byte-for-byte unchanged
    assert "max_queue" not in BatchingPolicy(max_batch=4).to_json()
    with pytest.raises(ValueError, match="max_queue"):
        BatchingPolicy(max_batch=4, max_queue=0)


def test_bounded_lane_raises_lane_saturated(plan_setup):
    cfg, params, masks, x, _ = plan_setup
    bank = SplitFnBank(params, cfg, masks, True)
    edge_fn, cloud_fn, _ = bank.get(SPLIT)
    feat = np.asarray(edge_fn(jax.numpy.asarray(x)))
    ref = np.asarray(cloud_fn(feat))     # the engine's bit-identity oracle
    started, gate = threading.Event(), threading.Event()

    def hold(c, rows):
        started.set()
        gate.wait(10.0)

    engine = DynamicBatcher(bank,
                            BatchingPolicy(max_batch=1, max_wait_ms=1.0,
                                           max_queue=1),
                            invoke_cost=hold)
    try:
        f1 = engine.submit(SPLIT, LANE, feat)
        assert started.wait(10.0)            # batch 1 holds the lane
        f2 = engine.submit(SPLIT, LANE, feat)     # fills the bounded queue
        with pytest.raises(LaneSaturated):
            engine.submit(SPLIT, LANE, feat)
        gate.set()
        for f in (f1, f2):
            assert np.array_equal(np.asarray(f.result(timeout=10.0)), ref)
    finally:
        gate.set()
        engine.stop()


# ---------------------------------------------------------------------------
# e2e: kill a member
# ---------------------------------------------------------------------------
def test_kill_one_of_three_reroutes_bit_identical(plan_setup):
    _, _, _, x, want = plan_setup
    ports = (29543, 29544, 29545)
    plan = make_plan(plan_setup, ports[0], faults=fast_policy(),
                     routing=RoutingPolicy(ports=ports, dead_after_count=1))
    with serving.CloudFleet(plan) as fleet:
        with serving.connect(plan, backend="socket",
                             sleep_fn=lambda s: None) as sess:
            r0 = sess.infer(x)
            np.testing.assert_allclose(r0["logits"], want,
                                       rtol=1e-4, atol=1e-4)
            assert r0["fault"] == {"faults": 0, "retries": 0,
                                   "migrations": 0, "fallback": False}
            victim = sess._client._port
            assert victim == winner(ports)
            fleet.kill(victim)
            r1 = sess.infer(x)               # reroute + replay
            # the survivor runs the SAME deployed split: logits from the
            # rerouted replay are bit-identical to the pre-kill server's
            assert np.array_equal(np.asarray(r1["logits"]),
                                  np.asarray(r0["logits"]))
            assert r1["fault"]["fallback"] is False
            assert r1["fault"]["faults"] >= 1
            assert sess._client._port == winner(ports, exclude=(victim,))
            stats = sess.router.stats()
            assert stats["servers"][victim]["state"] == "dead"
            assert stats["reroutes_count"] >= 1
            # the surviving members keep serving cleanly
            r2 = sess.infer(x)
            assert np.array_equal(np.asarray(r2["logits"]),
                                  np.asarray(r0["logits"]))
            assert r2["fault"]["faults"] == 0


# ---------------------------------------------------------------------------
# e2e: rolling restart (drain every member, zero failed requests)
# ---------------------------------------------------------------------------
def test_rolling_drain_of_whole_fleet_zero_failed_requests(plan_setup):
    _, _, _, x, want = plan_setup
    ports = (29546, 29547, 29548)
    plan = make_plan(plan_setup, ports[0], faults=fast_policy(),
                     routing=RoutingPolicy(ports=ports))
    migrations = 0
    with serving.CloudFleet(plan) as fleet:
        with serving.connect(plan, backend="socket",
                             sleep_fn=lambda s: None) as sess:
            ref = np.asarray(sess.infer(x)["logits"])
            np.testing.assert_allclose(ref, want, rtol=1e-4, atol=1e-4)
            for _ in range(len(ports)):      # one round per member
                victim = sess._client._port
                fleet.drain(victim)
                assert fleet.server(victim).draining
                for _ in range(2):
                    r = sess.infer(x)
                    assert np.array_equal(np.asarray(r["logits"]), ref)
                    # a drain migration is NOT a fault: the request
                    # replays on another member without failing
                    assert r["fault"]["faults"] == 0
                    assert r["fault"]["fallback"] is False
                    migrations += r["fault"]["migrations"]
                assert sess._client._port != victim
                fleet.restart(victim)
                sess.router.revive(victim)
                assert sess.router.state(victim) == "healthy"
    assert migrations == len(ports)          # each round migrated once


# ---------------------------------------------------------------------------
# e2e: whole fleet gone -> edge-only bottom rung
# ---------------------------------------------------------------------------
def test_whole_fleet_down_degrades_to_edge_only_parity(plan_setup):
    _, _, _, x, want = plan_setup
    ports = (29549, 29550)
    plan = make_plan(plan_setup, ports[0], faults=fast_policy(),
                     routing=RoutingPolicy(ports=ports, dead_after_count=1))
    with serving.CloudFleet(plan) as fleet:
        with serving.connect(plan, backend="socket",
                             sleep_fn=lambda s: None) as sess:
            np.testing.assert_allclose(sess.infer(x)["logits"], want,
                                       rtol=1e-4, atol=1e-4)
            for p in ports:
                fleet.kill(p)
            r = sess.infer(x)
            assert r["fault"]["fallback"] is True
            assert r["fault"]["faults"] >= 2     # both members were tried
            assert r["tx_bytes"] == 0            # nothing crossed the wire
            # the bottom rung serves the SAME deployed network (c=N)
            np.testing.assert_allclose(r["logits"], want,
                                       rtol=1e-4, atol=1e-4)
            assert sess.router.healthy_ports() == ()


# ---------------------------------------------------------------------------
# e2e: overload backpressure (BUSY)
# ---------------------------------------------------------------------------
def test_saturated_lane_sheds_busy_instead_of_stalling(plan_setup):
    _, _, _, x, want = plan_setup
    port = 29551
    plan = make_plan(plan_setup, port,
                     batching=BatchingPolicy(max_batch=1, max_wait_ms=1.0,
                                             max_queue=1))
    cfg, params, masks, _, _ = plan_setup
    bank = SplitFnBank(params, cfg, masks, True)
    edge_fn, _, _ = bank.get(SPLIT)
    payload = encode_feature(np.asarray(edge_fn(jax.numpy.asarray(x))),
                             codec="fp32")
    started, gate = threading.Event(), threading.Event()

    def hold(c, rows):
        started.set()
        gate.wait(10.0)

    srv = serving.CloudServer(plan, max_clients=1, verify=False)
    # the live engine (adopted before the server listens) holds its
    # first batch, so back-to-back raw frames overflow the bounded lane
    srv._engine.invoke_cost = hold
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
            s.settimeout(10.0)
            for _ in range(3):               # burst: no reads in between
                s.sendall(struct.pack("<Q", len(payload)) + payload)
            # release the lane only once frames 2 and 3 have each been
            # queued behind the held batch or shed
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not (
                    started.is_set()
                    and srv.fault_stats.get("busy_shed", 0) + sum(
                        r["pending"] for r in srv.lane_stats().values())
                    >= 2):
                time.sleep(0.005)
            gate.set()
            replies = []
            for _ in range(3):
                (n,) = struct.unpack("<Q", _read_exact(s, 8))
                replies.append(_read_exact(s, n))
    finally:
        gate.set()
        srv.stop()
    busy = [b for b in replies if is_busy(b)]
    served = [np.asarray(decode_tensor(b)[0])
              for b in replies if not is_busy(b)]
    assert len(busy) >= 1                    # the bound shed, no stall
    for b in busy:
        assert decode_busy(b)[0] == "queue"
    for logits in served:                    # the admitted rows still serve
        np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    assert srv.fault_stats.get("busy_shed", 0) == len(busy)


def _read_exact(s: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = s.recv(n - len(buf))
        if not chunk:
            raise EOFError("peer closed")
        buf += chunk
    return buf


def test_busy_reply_redirects_to_another_member(plan_setup):
    _, _, _, x, want = plan_setup
    ports = (29552, 29553)
    hot = winner(ports)                      # where the lane hashes first
    cold = next(p for p in ports if p != hot)
    stop = threading.Event()

    def always_busy():
        """A member whose lanes are permanently saturated: every data
        frame is answered with BUSY(redirect)."""
        lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lst.bind(("127.0.0.1", hot))
        lst.listen(4)
        lst.settimeout(0.2)
        conns = []
        try:
            while not stop.is_set():
                try:
                    c, _ = lst.accept()
                except socket.timeout:
                    continue
                conns.append(c)
                try:
                    (n,) = struct.unpack("<Q", _read_exact(c, 8))
                    _read_exact(c, n)
                    busy = encode_busy("queue", redirect=True)
                    c.sendall(struct.pack("<Q", len(busy)) + busy)
                except (EOFError, OSError, struct.error):
                    pass
        finally:
            for c in conns:
                c.close()
            lst.close()

    t = threading.Thread(target=always_busy, daemon=True)
    t.start()
    plan = make_plan(plan_setup, cold, faults=fast_policy(),
                     routing=RoutingPolicy(ports=ports))
    sleeps = []
    try:
        with serving.CloudServer(plan, port=cold, max_clients=None,
                                 verify=False):
            with serving.connect(plan, backend="socket", verify=False,
                                 sleep_fn=sleeps.append) as sess:
                assert sess._client._port == hot
                r = sess.infer(x)
                np.testing.assert_allclose(r["logits"], want,
                                           rtol=1e-4, atol=1e-4)
                assert r["fault"]["migrations"] == 1
                assert r["fault"]["faults"] == 0
                assert sess._client._port == cold
                assert sess.router.stats()["reroutes_count"] >= 1
    finally:
        stop.set()
        t.join(timeout=5)
    assert sleeps == []                      # a redirect never backs off


# ---------------------------------------------------------------------------
# recovery plumbing: injectable backoff sleep
# ---------------------------------------------------------------------------
def test_sleep_fn_receives_the_deterministic_backoff(plan_setup):
    _, _, _, x, want = plan_setup
    port = 29554
    plan = make_plan(plan_setup, port,
                     faults=fast_policy(request_deadline_s=1.0))
    inj = serving.FaultInjector(
        serving.FaultSchedule("one_drop", (FaultEvent(0, "drop"),)))
    sleeps = []
    with serving.CloudServer(plan, max_clients=None):
        with serving.connect(plan, backend="socket", faults=inj,
                             sleep_fn=sleeps.append) as sess:
            r = sess.infer(x)
    np.testing.assert_allclose(r["logits"], want, rtol=1e-4, atol=1e-4)
    assert r["fault"]["faults"] == 1 and r["fault"]["retries"] == 1
    # jitter-free policy: the recorded pause IS backoff_s(0), proving
    # the injected sleep replaced time.sleep on the recovery path
    assert sleeps == [pytest.approx(plan.faults.backoff_s(0), abs=1e-9)]


# ---------------------------------------------------------------------------
# congestion-aware adaptive splitting
# ---------------------------------------------------------------------------
def test_note_congestion_waives_dwell_without_collapsing_estimate():
    from repro.core.collab.adaptive import (AdaptivePolicy,
                                            AdaptiveSplitController)
    from repro.core.partition.latency_model import cnn_layer_costs
    from repro.core.partition.profiles import (PAPER_SERVER, LinkProfile,
                                               TwoTierProfile)
    cfg = tiny_cnn_config(num_classes=7, hw=32)
    edge = ComputeProfile("mcu", flops_per_s=50e6, mem_bw=1e9,
                          overhead_s=1e-4)
    prof = TwoTierProfile(edge, PAPER_SERVER,
                          LinkProfile("wifi", bandwidth=50e6 / 8,
                                      rtt_s=1e-3))
    policy = AdaptivePolicy(candidates=(0, 3, 6, 13), ewma_alpha=1.0,
                            min_samples=1, hysteresis=0.05, dwell=2)
    ctl = AdaptiveSplitController.for_deployment(cfg, policy, 0, prof)
    fast, slow = 50e6 / 8, 2e6 / 8
    # at the deployment bandwidth the current split stays optimal (and
    # the dwell counter warms past its threshold)
    assert ctl.step(12_000, 12_000 / fast + 1e-3) is None
    assert ctl.step(12_000, 12_000 / fast + 1e-3) is None
    sw = ctl.step(12_000, 12_000 / slow + 1e-3)      # collapse: offload less
    assert sw is not None and sw.new_split != 0
    # the link heals, but dwell blocks the walk back...
    assert ctl.step(12_000, 12_000 / fast + 1e-3) is None
    # ...until fleet backpressure waives it: re-decide NOW at the
    # current (healthy) estimate — the congestion answer is a re-split,
    # not an outage-style estimator collapse
    sw2 = ctl.note_congestion()
    assert sw2 is not None and sw2.new_split == 0
    assert ctl.estimator.bandwidth == pytest.approx(fast)


# ---------------------------------------------------------------------------
# fleet simulator chaos events
# ---------------------------------------------------------------------------
def test_chaos_event_roundtrip_validation_and_scenario_fold():
    ev = ChaosEvent(t_s=5.0, kind="kill", cloudlet=1)
    assert ChaosEvent.from_json(ev.to_json()) == ev
    with pytest.raises(ValueError, match="kind"):
        ChaosEvent(t_s=1.0, kind="meteor")
    with pytest.raises(ValueError, match="t_s"):
        ChaosEvent(t_s=-1.0, kind="kill")
    calm = FleetScenario(name="calm", seed=3, n_edges=50)
    assert "chaos" not in calm.to_json()     # pre-chaos digests unchanged
    stormy = FleetScenario(name="storm", seed=3, n_edges=50,
                           chaos=(ev, ChaosEvent(t_s=9.0, kind="revive",
                                                 cloudlet=1)))
    assert FleetScenario.from_json(stormy.to_json()).chaos == stormy.chaos
    with pytest.raises(ValueError, match="ChaosEvent"):
        FleetScenario(name="bad", chaos=({"t_s": 1.0},))


def test_fleet_sim_chaos_reroutes_deterministically():
    base = dict(seed=17, n_edges=150, n_cloudlets=3, duration_s=20.0)
    calm = simulate_fleet(FleetScenario(name="calm", **base))
    assert calm["chaos_reroutes_count"] == 0
    chaos = (ChaosEvent(t_s=5.0, kind="kill", cloudlet=0),
             ChaosEvent(t_s=8.0, kind="drain", cloudlet=1),
             ChaosEvent(t_s=14.0, kind="revive", cloudlet=0))
    sc = FleetScenario(name="storm", chaos=chaos, **base)
    r = simulate_fleet(sc)
    assert r["chaos_reroutes_count"] > 0     # orphans + arrivals moved
    assert r == simulate_fleet(sc)           # virtual clock: bit-identical
