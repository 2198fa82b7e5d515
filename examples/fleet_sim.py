"""Fleet-scale simulation sweep: fleet size x cloudlet count x SLO mix.

    PYTHONPATH=src python examples/fleet_sim.py [--smoke] [--json]

The paper validates one edge against one cloud; the real question for
the ROADMAP's "millions of users" north star is what happens when
thousands of heterogeneous, battery-constrained, wireless edges share
a cloudlet tier. This example drives ``repro.core.fleet`` — the
virtual-clock discrete-event simulator — over a grid of scenarios and
reports the numbers a fleet operator stares at: p50/p99 end-to-end
latency, joules per request, % deadlines met, and % shed per reason,
plus per-tier utilization and batching efficiency.

Three properties are asserted, not just reported:

  1. **Scale** — the headline cell simulates >= 1000 heterogeneous
     edges through the full edge -> cloudlet -> cloud hierarchy in
     well under 60 s wall-clock (virtual time is decoupled from wall
     time, so 10k-edge cells are minutes of traffic in seconds).
  2. **Determinism** — the headline scenario runs twice with the same
     seed and must produce bit-identical rollups (the contract the
     virtual clock + seeded arrival streams exist to provide; no
     wall-clock value ever enters a rollup).
  3. **Conservation** — every arrival is accounted: served (collab or
     degraded-to-edge) + shed == arrivals, per cell.

``--smoke`` runs the CI-sized grid; ``--json`` prints the headline
cell's rollup plus each cell's summary keys as one JSON object on the
last line of stdout. All of it is virtual-clock, so bit-identical
across same-seed runs.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

from repro.core.collab.faults import FaultPolicy
from repro.core.fleet import (DEFAULT_SLO_CLASSES, FleetScenario, SLOClass,
                              simulate_fleet)

#: a deadline-heavy traffic mix: everything is interactive-or-standard,
#: deadlines twice as tight as the default — the cell that makes the
#: admission controller and the cloudlet spillover actually sweat
STRICT_SLO_CLASSES = (
    SLOClass("interactive", 0.50,
             FaultPolicy(request_deadline_s=0.15, fallback="edge",
                         max_retries=0)),
    SLOClass("standard", 0.50,
             FaultPolicy(request_deadline_s=0.5, fallback="edge")),
)

SLO_MIXES = {"default": DEFAULT_SLO_CLASSES, "strict": STRICT_SLO_CLASSES}


def _cells(fast: bool) -> List[Dict]:
    """(fleet size, cloudlet count, SLO mix) grid; first cell is the
    headline the report leads with."""
    if fast:
        return [
            {"n_edges": 1000, "n_cloudlets": 8, "slo_mix": "default",
             "duration_s": 30.0},
            {"n_edges": 1000, "n_cloudlets": 2, "slo_mix": "strict",
             "duration_s": 30.0},
        ]
    return [
        {"n_edges": 1000, "n_cloudlets": 8, "slo_mix": "default",
         "duration_s": 60.0},
        {"n_edges": 2000, "n_cloudlets": 4, "slo_mix": "default",
         "duration_s": 60.0},
        {"n_edges": 5000, "n_cloudlets": 8, "slo_mix": "default",
         "duration_s": 60.0},
        {"n_edges": 10000, "n_cloudlets": 16, "slo_mix": "default",
         "duration_s": 60.0},
        {"n_edges": 10000, "n_cloudlets": 4, "slo_mix": "strict",
         "duration_s": 60.0},
    ]


def _scenario(cell: Dict, seed: int = 7) -> FleetScenario:
    return FleetScenario(
        name=f"{cell['slo_mix']}-{cell['n_edges']}x{cell['n_cloudlets']}",
        seed=seed, n_edges=cell["n_edges"],
        n_cloudlets=cell["n_cloudlets"], duration_s=cell["duration_s"],
        slo_classes=SLO_MIXES[cell["slo_mix"]])


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def table(rows: List[Dict], cols: List[str], title: str) -> str:
    widths = {c: max([len(c)] + [len(_fmt(r[c])) for r in rows])
              for c in cols}
    lines = [f"== {title} ==",
             " | ".join(c.ljust(widths[c]) for c in cols),
             "-+-".join("-" * widths[c] for c in cols)]
    lines += [" | ".join(_fmt(r[c]).ljust(widths[c]) for c in cols)
              for r in rows]
    return "\n".join(lines)


def record(res: Dict) -> Dict:
    """The flat summary ``--json`` prints: the headline scenario's full
    rollup (fleet p50/p99, joules/request, deadline attainment, per-tier
    shed/utilization/queue metrics) plus each cell's summary keys."""
    rec: Dict = dict(res["headline"])
    rec["determinism_ok"] = res["determinism_ok"]
    for row in res["rows"]:
        k = (f"{row['slo_mix']}_{row['n_edges']}edges"
             f"_{row['n_cloudlets']}cl")
        for m in ("deadline_met_frac", "shed_frac", "latency_p99_s",
                  "cloud_util"):
            rec[f"{k}_{m}"] = row[m]
    return rec


def run(fast: bool = False) -> Dict:
    cells = _cells(fast)
    rows: List[Dict] = []
    headline = None
    wall_total = 0.0
    for cell in cells:
        sc = _scenario(cell)
        t0 = time.perf_counter()   # wall-clock: sweep speed report only
        rollup = simulate_fleet(sc)
        wall = time.perf_counter() - t0   # wall-clock: never in a rollup
        wall_total += wall
        assert rollup["arrivals"] == rollup["served"] + rollup["shed"], (
            f"arrival conservation broken in {sc.name}")
        print(f"{sc.describe()}\n  -> {rollup['arrivals']} arrivals in "
              f"{wall:.1f}s wall ({cell['duration_s']:g}s virtual)")
        rows.append({
            "slo_mix": cell["slo_mix"], "n_edges": cell["n_edges"],
            "n_cloudlets": cell["n_cloudlets"],
            "arrivals": rollup["arrivals"],
            "deadline_met_frac": rollup["deadline_met_frac"],
            "shed_frac": rollup["shed_frac"],
            "latency_p50_s": rollup["latency_p50_s"],
            "latency_p99_s": rollup["latency_p99_s"],
            "joules_per_req": rollup["edge_joules_per_request"],
            "cloudlet_util": rollup["cloudlet_util"],
            "cloud_util": rollup["cloud_util"],
            "cloud_avg_batch": rollup["cloud_avg_batch"],
        })
        if headline is None:
            headline = rollup
            # acceptance: >= 1000 edges through the hierarchy, fast
            assert sc.n_edges >= 1000 and wall < 60.0, (
                f"headline cell too slow/small: {sc.n_edges} edges, "
                f"{wall:.1f}s wall")
            # acceptance: bit-identical rollup on a same-seed re-run
            rerun = simulate_fleet(_scenario(cell))
            assert rerun == rollup, "same-seed rollups differ"
    print("\n" + table(rows, ["slo_mix", "n_edges", "n_cloudlets",
                              "arrivals", "deadline_met_frac", "shed_frac",
                              "latency_p50_s", "latency_p99_s",
                              "joules_per_req", "cloudlet_util",
                              "cloud_util", "cloud_avg_batch"],
                       title="fleet sweep (virtual clock)"))
    # per-SLO-class detail of the headline cell
    slo_rows = []
    for cls in DEFAULT_SLO_CLASSES:
        k = cls.name
        slo_rows.append({
            "class": k, "deadline_s": cls.deadline_s,
            "arrivals": headline[f"{k}_arrivals"],
            "met_frac": headline[f"{k}_deadline_met_frac"],
            "shed_frac": headline[f"{k}_shed_frac"],
            "p50_s": headline[f"{k}_latency_p50_s"],
            "p99_s": headline[f"{k}_latency_p99_s"],
        })
    print("\n" + table(slo_rows, ["class", "deadline_s", "arrivals",
                                  "met_frac", "shed_frac", "p50_s",
                                  "p99_s"],
                       title="headline cell, per SLO class"))
    print(f"\ntotal sweep wall-clock: {wall_total:.1f}s "
          f"(virtual: {sum(c['duration_s'] for c in cells):g}s)")
    # wall seconds stay OUT of the returned payload's headline/rows —
    # they would break the bit-identical determinism contract
    return {"headline": headline, "rows": rows, "determinism_ok": True}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grid (2 cells, 30s virtual each)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as JSON on the last line")
    args = ap.parse_args()
    res = run(fast=args.smoke)
    if args.json:
        print(json.dumps(record(res), sort_keys=True))
