"""Fleet simulator tests: virtual clock, seeded population, admission,
tier batching, scenario serialization, and the determinism regression
the fleet-sim report depends on."""
import random

import pytest

from repro.core.collab.batching import BatchingPolicy
from repro.core.collab.faults import FaultPolicy
from repro.core.fleet import (DEFAULT_SLO_CLASSES, ArrivalPattern,
                              EventQueue, FleetScenario, FleetSimulator,
                              SLOClass, TierServer, build_population,
                              percentile, simulate_fleet)
from repro.core.fleet.population import DEVICE_CLASSES
from repro.core.fleet.tiers import CLOUDLET_SERVER
from repro.core.partition.energy_model import (ENERGY_PROFILES,
                                               PHONE_ENERGY,
                                               urgency_scaled_weight)
from repro.core.partition.latency_model import (LayerCost,
                                                batched_segment_time,
                                                batched_server_time)
from repro.core.partition.profiles import PHONE_EDGE, PI_EDGE

pytestmark = pytest.mark.fleet


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------
def test_event_queue_fires_in_time_then_insertion_order():
    q = EventQueue()
    fired = []
    q.push(2.0, lambda: fired.append("late"))
    q.push(1.0, lambda: fired.append("early"))
    q.push(1.0, lambda: fired.append("early2"))   # same t: insertion order
    n = q.run_until()
    assert n == 3
    assert fired == ["early", "early2", "late"]
    assert q.now == 2.0


def test_event_queue_clamps_past_times_and_nests():
    q = EventQueue()
    fired = []

    def first():
        fired.append(q.now)
        q.push(q.now - 5.0, lambda: fired.append(q.now))  # clamped to now

    q.push(1.0, first)
    q.run_until()
    assert fired == [1.0, 1.0]                    # never moves backwards


def test_event_queue_horizon_stops_early():
    q = EventQueue()
    fired = []
    q.push(1.0, lambda: fired.append(1))
    q.push(5.0, lambda: fired.append(5))
    q.run_until(horizon=2.0)
    assert fired == [1] and len(q) == 1


# ---------------------------------------------------------------------------
# profiles satellite
# ---------------------------------------------------------------------------
def test_phone_class_sits_between_pi_and_server():
    assert PI_EDGE.flops_per_s < PHONE_EDGE.flops_per_s
    assert PHONE_EDGE.flops_per_s < CLOUDLET_SERVER.flops_per_s
    assert ENERGY_PROFILES["phone"] is PHONE_ENERGY
    # a phone burns more active power than the Pi-class board's SoC
    assert PHONE_ENERGY.compute_power_w > 0
    assert PHONE_ENERGY.radio.tx_power_w > PHONE_ENERGY.radio.idle_power_w


def test_urgency_scaled_weight_shared_formula():
    w = 0.02
    assert urgency_scaled_weight(w, None) == w
    assert urgency_scaled_weight(w, 1.0) == pytest.approx(w)
    assert urgency_scaled_weight(w, 0.5) == pytest.approx(w * 4)
    # floor keeps a dead battery finite
    assert urgency_scaled_weight(w, 0.0) == pytest.approx(w / 1e-6)


def test_batched_segment_time_generalizes_batched_server_time():
    costs = [LayerCost(i, f"l{i}", 1e9, 1e5) for i in range(5)]
    assert batched_segment_time(costs, 2, 5, CLOUDLET_SERVER, 4) \
        == pytest.approx(batched_server_time(costs, 2, CLOUDLET_SERVER, 4))
    with pytest.raises(ValueError):
        batched_segment_time(costs, 3, 2, CLOUDLET_SERVER, 1)
    with pytest.raises(ValueError):
        batched_segment_time(costs, 0, 5, CLOUDLET_SERVER, 0)


# ---------------------------------------------------------------------------
# scenario + plan section
# ---------------------------------------------------------------------------
def test_scenario_roundtrips_through_json():
    sc = FleetScenario(name="rt", seed=11, n_edges=50, n_cloudlets=3,
                       duration_s=12.0)
    assert FleetScenario.from_json(sc.to_json()) == sc


def test_scenario_validates_mixes_and_batteries():
    with pytest.raises(ValueError, match="shares sum"):
        FleetScenario(name="bad", device_mix=(("mcu", 0.5), ("pi", 0.2)))
    with pytest.raises(ValueError, match="unknown device class"):
        FleetScenario(name="bad", device_mix=(("gpu", 1.0),),
                      battery_j=(("gpu", 10.0),))
    with pytest.raises(ValueError, match="battery_j"):
        FleetScenario(name="bad", battery_j=(("mcu", 0.0),))
    with pytest.raises(ValueError, match="share"):
        SLOClass("x", 0.0, FaultPolicy())


def test_plan_fleet_section_folds_into_digest_only_when_set(tmp_path):
    import jax
    from repro import serving
    from repro.models.cnn import init_cnn_params, tiny_cnn_config
    cfg = tiny_cnn_config(num_classes=5, hw=32)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    bare = serving.DeploymentPlan.from_args(params, cfg, 3)
    sc = FleetScenario(name="study", seed=5, n_edges=100)
    fleet = serving.DeploymentPlan.from_args(params, cfg, 3, fleet=sc)
    assert bare.digest != fleet.digest          # section is contract-level
    assert "fleet" not in bare.contract()       # only-when-set precedent
    assert fleet.contract()["fleet"] == sc.to_json()
    path = fleet.save(str(tmp_path / "deploy"))
    reloaded = serving.DeploymentPlan.load(path)
    assert reloaded.fleet == sc
    assert reloaded.digest == fleet.digest
    assert "fleet=study" in fleet.describe()


# ---------------------------------------------------------------------------
# population
# ---------------------------------------------------------------------------
def test_population_is_seed_deterministic_and_heterogeneous():
    sc = FleetScenario(name="pop", seed=4, n_edges=400)
    a, b = build_population(sc), build_population(sc)
    assert [(e.device_class, e.trace.name, e.slo.name, e.trace_phase,
             e.cloudlet_id) for e in a] \
        == [(e.device_class, e.trace.name, e.slo.name, e.trace_phase,
             e.cloudlet_id) for e in b]
    classes = {e.device_class for e in a}
    assert classes == set(DEVICE_CLASSES)       # all three classes present
    assert len({e.trace.name for e in a}) > 1
    # shares land near the mix (law of large numbers, fixed seed)
    mcu = sum(1 for e in a if e.device_class == "mcu") / len(a)
    assert 0.15 < mcu < 0.35
    # batteries start full, per class
    for e in a:
        assert e.battery_left_j == sc.battery_for(e.device_class)


def test_arrivals_are_seeded_and_diurnal():
    sc = FleetScenario(name="arr", seed=9, n_edges=1)
    edge = build_population(sc)[0]
    ts, t = [], 0.0
    for _ in range(200):
        t = edge.next_arrival(t, sc.arrival)
        ts.append(t)
    assert ts == sorted(ts) and len(set(ts)) == len(ts)
    edge2 = build_population(sc)[0]
    t2 = [edge2.next_arrival(0.0, sc.arrival)]
    for _ in range(199):
        t2.append(edge2.next_arrival(t2[-1], sc.arrival))
    assert ts == t2                             # same seed, same stream
    # long-run mean rate within the diurnal envelope
    rate = len(ts) / ts[-1]
    assert (sc.arrival.base_rate_hz * 0.5 < rate
            < sc.arrival.peak_rate_hz * 1.5)


# ---------------------------------------------------------------------------
# tiers
# ---------------------------------------------------------------------------
def _costs(n=6):
    return [LayerCost(i, f"l{i}", 2e9, 1e5) for i in range(n)]


def test_tier_server_fuses_concurrent_arrivals_into_one_batch():
    q = EventQueue()
    srv = TierServer("t", CLOUDLET_SERVER,
                     BatchingPolicy(max_batch=8, max_wait_ms=5.0),
                     _costs(), q)
    done = []
    for i in range(3):
        assert srv.submit((2, 6), i, lambda p, t: done.append((p, t)))
    q.run_until()
    assert [p for p, _ in done] == [0, 1, 2]
    assert srv.stats.batches == 1 and srv.stats.rows == 3
    # padded up to the policy's bucket (power-of-two default: 4)
    assert srv.stats.padded_rows == 1
    # all three finished together, after the window + one fused service
    t_done = {t for _, t in done}
    assert len(t_done) == 1
    t_serve = batched_segment_time(_costs(), 2, 6, CLOUDLET_SERVER, 4)
    assert t_done.pop() == pytest.approx(5e-3 + t_serve)


def test_tier_server_sheds_at_queue_bound():
    q = EventQueue()
    srv = TierServer("t", CLOUDLET_SERVER,
                     BatchingPolicy(max_batch=2, max_wait_ms=1.0),
                     _costs(), q, max_queue=2)
    assert srv.submit((0, 6), "a", lambda p, t: None)
    assert srv.submit((0, 6), "b", lambda p, t: None)
    assert not srv.submit((0, 6), "c", lambda p, t: None)
    assert srv.stats.shed == 1


def test_tier_server_separates_lanes_by_segment():
    q = EventQueue()
    srv = TierServer("t", CLOUDLET_SERVER,
                     BatchingPolicy(max_batch=8, max_wait_ms=1.0),
                     _costs(), q)
    done = []
    srv.submit((1, 6), "seg16", lambda p, t: done.append(p))
    srv.submit((3, 6), "seg36", lambda p, t: done.append(p))
    q.run_until()
    assert sorted(done) == ["seg16", "seg36"]
    assert srv.stats.batches == 2               # different shapes never fuse


# ---------------------------------------------------------------------------
# end-to-end + determinism regression
# ---------------------------------------------------------------------------
def test_fleet_run_conserves_arrivals_and_uses_every_route():
    sc = FleetScenario(name="e2e", seed=3, n_edges=300, n_cloudlets=2,
                       duration_s=20.0)
    r = simulate_fleet(sc)
    assert r["arrivals"] == r["served"] + r["shed"]
    assert r["served_collab"] > 0 and r["served_edge_only"] > 0
    assert 0.0 < r["deadline_met_frac"] <= 1.0
    assert r["latency_p50_s"] <= r["latency_p99_s"]
    assert r["edge_joules_per_request"] > 0
    assert r["cloudlet_rows"] > 0
    assert r["uplink_mb_total"] > 0


def test_fleet_same_seed_rollups_are_bit_identical():
    # the determinism regression the fleet-sim report depends on: same
    # scenario seed -> byte-identical metrics, run to run
    sc = FleetScenario(name="det", seed=21, n_edges=250, n_cloudlets=3,
                       duration_s=15.0)
    assert simulate_fleet(sc) == simulate_fleet(sc)


def test_fleet_seed_actually_matters():
    a = simulate_fleet(FleetScenario(name="s", seed=1, n_edges=200,
                                     duration_s=10.0))
    b = simulate_fleet(FleetScenario(name="s", seed=2, n_edges=200,
                                     duration_s=10.0))
    assert a != b


def test_battery_exhaustion_sheds_and_degrades():
    # microscopic batteries: edges exhaust quickly and later arrivals
    # shed with reason "battery"
    sc = FleetScenario(name="drain", seed=6, n_edges=100, n_cloudlets=2,
                       duration_s=30.0,
                       battery_j=(("mcu", 0.5), ("pi", 0.5),
                                  ("phone", 0.5)))
    sim = FleetSimulator(sc)
    r = sim.run()
    assert r["exhausted_edges"] > 0
    assert r["shed_battery_frac"] > 0
    # exhausted edges stopped paying joules after their budget
    for e in sim.edges:
        assert e.battery_left_j >= 0.0


def test_strict_slo_sheds_more_than_lenient():
    strict = (SLOClass("tight", 1.0,
                       FaultPolicy(request_deadline_s=0.03,
                                   fallback="fail")),)
    lenient = (SLOClass("loose", 1.0,
                        FaultPolicy(request_deadline_s=30.0,
                                    fallback="edge")),)
    base = dict(seed=5, n_edges=150, n_cloudlets=2, duration_s=10.0)
    r_strict = simulate_fleet(FleetScenario(name="st",
                                            slo_classes=strict, **base))
    r_lenient = simulate_fleet(FleetScenario(name="le",
                                             slo_classes=lenient, **base))
    assert r_strict["shed_frac"] > r_lenient["shed_frac"]
    assert r_lenient["deadline_met_frac"] >= r_strict["deadline_met_frac"]


def test_percentile_pure_python():
    assert percentile([], 99) == 0.0
    assert percentile([5.0], 50) == 5.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0
    xs = [random.Random(0).random() for _ in range(100)]
    assert min(xs) <= percentile(xs, 1) <= percentile(xs, 99) <= max(xs)
