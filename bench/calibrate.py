"""Read the compared number of a cell over many seeds, for the program as
the configuration states it and for its control, to set the cell's limit.

    python bench/calibrate.py --workload <cell> --seconds 3 \\
        --seeds 11 12 ... --control-seeds 21 22 23

Run by hand on the chip, one process for all seeds: each seed deploys the
cell afresh and serves a short window of the cell's own traffic. The
control is the program's own int4 edge-weight path (``QuantPolicy
(weight_bits=4)``), the nearest precision below the configuration's int8.
Prints one JSON line per run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

CONTROL_BITS = 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()

    from bench import model, peaks, run
    from repro import compile_cache
    compile_cache.enable()
    devs = run.check_chip(1)
    peak = peaks.peaks_for(devs[0].device_kind)
    bench = run.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = model.load_config(cell["config"])
    traffic = run.load_traffic(cell["traffic"])
    e2e, _ = run.cell_metrics(bench, args.workload)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    jobs = ([(s, False) for s in args.seeds]
            + [(s, True) for s in args.control_seeds])
    for seed, control in jobs:
        res = run.run_cell(cfg, traffic, [(m, units[m]) for m in e2e],
                           seed=seed, seconds=args.seconds, trace=False,
                           limit=None,
                           t_start=time.perf_counter(), peaks=peak,
                           weight_bits=CONTROL_BITS if control else None)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "control": control,
                           "logit_err": res["checks"]["logit_err"]["value"],
                           "failed": res["failed"],
                           "attempted": res["attempted"],
                           "metrics": {k: v["value"] for k, v
                                       in res["metrics"].items()}})
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
