"""Sweep the offered rate of an open loop over one deployment, to find the
highest rate the system sustains (the knee) once, by hand, on the chip.

    python bench/sweep.py --config alexnet224 --traffic steady \\
        --rates 200 300 400 500 --seconds 6 --seed 7

One process deploys the configuration with the traffic file's edges and
then offers each rate in turn for ``--seconds``. Per rate it prints the
completed rate, p50 and p95 latency from when each request was due, and
how late the generator ran, as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

#: request ids of one rate's window (ids of two windows never meet)
IDS_PER_RATE = 20000


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    from bench import loadgen, model, run
    from repro import compile_cache
    compile_cache.enable()
    run.check_chip(1)
    cfg = model.load_config(args.config)
    traffic = run.load_traffic(args.traffic)
    with run.served(cfg, traffic, args.seed) as cell:
        for k, rate in enumerate(args.rates):
            mix = dict(traffic, loop="open", rate_per_s=rate)
            win = loadgen.start(cell.sessions, mix, cell.image, args.seconds,
                                model.seed_rng(args.seed, 10 + k),
                                first_id=(k + 1) * IDS_PER_RATE)
            win.join(args.seconds + 300)
            rec = {"finished": win.finished, "t0": win.t0}
            print(json.dumps({"rate_per_s": rate,
                              "offered": len(win.requests),
                              "failed": len(win.failed),
                              **{m: run.reader(m).read(rec) for m in
                                 ("req_per_s", "p50_ms", "p95_ms")},
                              **loadgen.lateness(win)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
