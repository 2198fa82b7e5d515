"""Benchmark harness entry point — one benchmark per paper table/figure,
plus the kernel sweeps and the roofline aggregation.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (adaptive_split, cloud_batching, collab_throughput,
                        energy_split, fault_injection, fig4_layerwise,
                        fig5_methods, fleet_sim, kernel_edge,
                        kernels_bench, roofline_report, table1_accuracy,
                        table2_split_latency)
from benchmarks.common import (write_collab_record, write_energy_record,
                               write_faults_record, write_fleet_record,
                               write_kernels_record)
from repro import compile_cache

BENCHES = [
    ("table2_split_latency", table2_split_latency.run),
    ("fig4_layerwise", fig4_layerwise.run),
    ("fig5_methods", fig5_methods.run),
    ("collab_throughput", collab_throughput.run),
    ("cloud_batching", cloud_batching.run),
    ("adaptive_split", adaptive_split.run),
    ("energy_split", energy_split.run),
    ("fault_injection", fault_injection.run),
    ("fleet_sim", fleet_sim.run),
    ("kernels_micro", kernels_bench.run),
    ("kernel_edge", kernel_edge.run),
    ("table1_accuracy", table1_accuracy.run),
    ("roofline", roofline_report.run),
]


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes/epochs for CI-style runs")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write the tracked BENCH_collab.json perf record "
                         "(req/s, p50/p95, tx bytes, padding waste, "
                         "streaming req/s) from the collab-serving "
                         "results of this pass")
    args = ap.parse_args()
    failures = []
    results = {}
    for name, fn in BENCHES:
        if args.only and args.only != name:
            continue
        print(f"\n######## {name} ########")
        t0 = time.time()
        try:
            results[name] = fn(fast=args.fast)
            print(f"######## {name}: OK ({time.time() - t0:.1f}s)")
        except Exception:                               # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"######## {name}: FAILED")
    if args.json and "cloud_batching" in results:
        fn = write_collab_record(results["cloud_batching"],
                                 results.get("collab_throughput"))
        print(f"\nperf record: {fn}")
    if args.json and "energy_split" in results:
        print(f"perf record: {write_energy_record(results['energy_split'])}")
    if args.json and "fault_injection" in results:
        print("perf record: "
              f"{write_faults_record(results['fault_injection'])}")
    if args.json and "fleet_sim" in results:
        print(f"perf record: {write_fleet_record(results['fleet_sim'])}")
    if args.json and "kernel_edge" in results:
        print("perf record: "
              f"{write_kernels_record(results['kernel_edge'])}")
    if failures:
        sys.exit(f"benchmark failures: {failures}")
    print("\nall benchmarks passed")


if __name__ == "__main__":
    main()
