"""The annotated invariant registry the checkers run from.

Three maps, one per checker:

* ``CONCURRENCY`` — every shared-mutable attribute of the collab serving
  stack, annotated with the lock that must guard its writes, or — when a
  single thread owns it by construction — ``lock=None`` plus a
  justification note. The concurrency checker verifies the locked
  entries lexically and the registry itself doubles as a drift detector:
  an entry whose class or attribute no longer exists in the source is a
  ``stale-registry`` finding, so deleting or renaming state forces the
  annotation to move with it.

* ``PURITY_SCOPES`` — the virtual-clock domain: files (or single classes
  inside mixed files) where wall-clock reads, ``time.sleep`` and
  module-level ``random`` are forbidden because the fleet simulator's
  same-seed bit-identity contract dies the moment one sneaks in.

* ``SERIALIZATION`` — the serializable plan sections whose JSON keys
  must carry unit suffixes, the ``DeploymentPlan`` optional sections
  that must follow the digest fold-only-when-set rule, and the wire
  codec module whose ``struct.pack`` formats need byte-compatible
  ``unpack`` twins.

Paths are repo-relative posix suffixes; the runner matches them against
``str(file).endswith(suffix)`` so the registry works from any checkout
location.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class SharedAttr:
    """One shared-mutable attribute of a class in the serving stack.

    ``lock`` names the instance attribute (``with self.<lock>:``) or
    closure name every write outside ``__init__`` must be lexically
    guarded by; ``lock=None`` declares single-thread ownership instead,
    and then ``note`` must say *why* that is safe — the checker rejects
    unjustified ownership claims.
    """
    cls: str
    attr: str
    lock: Optional[str]
    note: str = ""


@dataclass(frozen=True)
class ClosureVar:
    """A closure variable shared across threads spawned by a
    module-level function (e.g. ``serve_cloud``'s ``fault_stats`` dict,
    mutated by every handler/writer thread). Same lock/ownership
    semantics as ``SharedAttr``, with the lock being a closure name."""
    func: str
    var: str
    lock: Optional[str]
    note: str = ""


#: path suffix -> registered shared state in that module
CONCURRENCY: Dict[str, Tuple] = {
    "core/collab/runtime.py": (
        SharedAttr("SplitFnBank", "_fns", lock="_cache_lock"),
        SharedAttr("SplitFnBank", "n_traces", lock=None,
                   note="approximate diagnostic counter bumped inside "
                        "jax-traced closures; a lock cannot wrap a traced "
                        "body and an off-by-one trace count is harmless"),
        ClosureVar("serve_cloud", "fault_stats", lock="stats_lock"),
    ),
    "core/collab/batching.py": (
        SharedAttr("DynamicBatcher", "_lanes", lock="_lock"),
        SharedAttr("LaneStats", "rows", lock=None,
                   note="mutated only by the owning lane's single "
                        "scheduler thread; read after stop() joins it"),
        SharedAttr("LaneStats", "frames", lock=None,
                   note="single lane-scheduler-thread owner (see rows)"),
        SharedAttr("LaneStats", "batches", lock=None,
                   note="single lane-scheduler-thread owner (see rows)"),
        SharedAttr("LaneStats", "padded_rows", lock=None,
                   note="single lane-scheduler-thread owner (see rows)"),
        SharedAttr("LaneStats", "busy_s", lock=None,
                   note="single lane-scheduler-thread owner (see rows)"),
        SharedAttr("LaneStats", "queue_wait_s", lock=None,
                   note="single lane-scheduler-thread owner (see rows)"),
        SharedAttr("LaneStats", "failed_rows", lock=None,
                   note="single lane-scheduler-thread owner (see rows)"),
        SharedAttr("LaneStats", "cancelled_frames", lock=None,
                   note="written by the scheduler thread and by stop()'s "
                        "drain, which runs after _stop is set and the "
                        "scheduler has exited its pop loop"),
    ),
    "core/collab/channel.py": (
        SharedAttr("FaultInjector", "_attempt", lock="_lock"),
        SharedAttr("FaultInjector", "counts", lock="_lock"),
        SharedAttr("LinkShaper", "_budget", lock="_lock"),
        SharedAttr("LinkShaper", "_last", lock="_lock"),
        SharedAttr("ShapedSocket", "last_send_cost_s", lock=None,
                   note="one sender thread per connection by protocol "
                        "design; the reader thread never writes it"),
        SharedAttr("SimChannel", "sent_bytes", lock=None,
                   note="SimChannel is single-owner by contract: the "
                        "in-process runner or the one tx-stage thread"),
        SharedAttr("SimChannel", "elapsed_s", lock=None,
                   note="single-owner (see sent_bytes)"),
        SharedAttr("SimChannel", "last_send_events", lock=None,
                   note="single-owner (see sent_bytes)"),
    ),
    "core/collab/adaptive.py": (
        SharedAttr("BandwidthEstimator", "_ewma", lock="_lock"),
        SharedAttr("BandwidthEstimator", "n_samples", lock="_lock"),
        SharedAttr("AdaptiveSplitController", "split", lock="_lock"),
        SharedAttr("AdaptiveSplitController", "battery_j", lock="_lock"),
        SharedAttr("AdaptiveSplitController", "n_requests", lock="_lock"),
        SharedAttr("AdaptiveSplitController", "_since_switch",
                   lock="_lock"),
    ),
    "core/collab/streaming.py": (
        SharedAttr("StageStats", "busy_s", lock=None,
                   note="each pipeline stage charges only its own stats "
                        "object; read after join()"),
        SharedAttr("StageStats", "items", lock=None,
                   note="single-stage-thread owner (see busy_s)"),
        SharedAttr("StageStats", "batches", lock=None,
                   note="single-stage-thread owner (see busy_s)"),
    ),
    "core/collab/faults.py": (),     # pure-data policies: no shared state
    "core/collab/cluster.py": (
        SharedAttr("FleetRouter", "_state", lock="_lock"),
        SharedAttr("FleetRouter", "_miss", lock="_lock"),
        SharedAttr("FleetRouter", "_dead_at_s", lock="_lock"),
        SharedAttr("FleetRouter", "_routed", lock="_lock"),
        SharedAttr("FleetRouter", "_reroutes", lock="_lock"),
    ),
    "serving/session.py": (
        SharedAttr("CloudFleet", "_servers", lock="_lock"),
        SharedAttr("CloudServer", "_engine", lock=None,
                   note="written once by the serve thread before it sets "
                        "the ready event that __init__ waits on; read-only "
                        "after that"),
    ),
}

#: path suffix -> class names to scan (None = whole file). Everything
#: under core/fleet/ is added by the runner unconditionally.
PURITY_SCOPES: Dict[str, Optional[Tuple[str, ...]]] = {
    "core/collab/channel.py": ("SimChannel",),
    "core/partition/profiles.py": ("LinkTrace",),
    # the fleet-sim example drives the virtual clock; its two wall-clock
    # sweep-timing lines are pinned by justified `# wall-clock:` markers
    "examples/fleet_sim.py": None,
}

#: directory fragment whose every file is in the purity domain
PURITY_TREE = "core/fleet/"

#: path suffix -> classes whose ``to_json`` keys must be unit-suffixed
UNIT_SUFFIX_CLASSES: Dict[str, Tuple[str, ...]] = {
    "core/collab/batching.py": ("BatchingPolicy", "LaneStats"),
    "core/collab/faults.py": ("FaultPolicy",),
    "core/collab/adaptive.py": ("AdaptivePolicy",),
    "core/collab/cluster.py": ("RoutingPolicy",),
    "core/partition/energy_model.py": ("EnergyPolicy", "EnergyProfile"),
    "core/fleet/scenario.py": ("FleetScenario", "SLOClass",
                               "ArrivalPattern", "ChaosEvent"),
    "core/collab/quant.py": ("QuantPolicy",),
}

#: the DeploymentPlan optional sections under the fold-only-when-set rule
PLAN_PATH = "serving/plan.py"
PLAN_CLASS = "DeploymentPlan"
PLAN_METHOD = "contract"
PLAN_SECTIONS: Tuple[str, ...] = ("adaptive", "batching", "energy",
                                  "faults", "fleet", "quant", "routing")

#: the wire codec whose pack formats need unpack twins
PROTOCOL_PATH = "core/collab/protocol.py"
