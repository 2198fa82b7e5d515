"""Run one benchmark cell on the chip this process finds, and print its
result as the last line of standard output.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). One process holds one chip: it makes the
weights, masks and images from ``--seed``, deploys the configuration's plan
through the program's ``CloudServer`` and socket edge sessions (threads of
this process), warms every shape the traffic uses, and measures for
``--seconds``. Every metric of
``BENCHMARK.json`` is read from the run's record by its own reader,
``bench/metrics/<metric>.py``. With ``--trace 1`` the run traces the end of
the window and prints the cell's per-layer metrics in place of its
end-to-end ones; the readers that need no trace read the part of the window
before the trace began.

Once the window has closed it compares a seeded sample of the answers
served in the window with the plain float32 reference (``reference.py``)
and prints each number compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import gc            # noqa: E402
import glob          # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import socket        # noqa: E402
import sys           # noqa: E402
import types         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

#: requests of the window compared with the reference
SAMPLE = 256
#: images the reference runs at once
REF_BLOCK = 32
#: where a traced run writes its trace (inside the checkout, removed after)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_benchmark(path: str = os.path.join(ROOT, "BENCHMARK.json")):
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def cell_metrics(bench, workload: str):
    """(end-to-end metric names, per-layer metric names) the cell reports."""
    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m["name"] for m in bench["end_to_end"] if mine(m)]
    per = [m["name"] for m in bench["per_layer"] if mine(m)]
    return e2e, per


def reader(metric: str):
    """A metric's reader: ``metrics/<name>.py``, or the module of
    its name up to the first dot (``device_idle.sat`` reads with
    ``metrics/device_idle.py``)."""
    for mod in (metric, metric.split(".")[0]):
        if os.path.exists(os.path.join(HERE, "metrics", mod + ".py")):
            return importlib.import_module(f"bench.metrics.{mod}")
    raise FileNotFoundError(f"no reader for metric {metric!r}")


def check_chip(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device ({e})")
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform!r}, not on a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


def run_cell(cfg, traffic, metrics, *, seed: int, seconds: float,
             trace: bool, limit, t_start: float, weight_bits=None,
             peaks=None):
    """Serve one window of ``traffic`` on ``cfg``, read ``metrics`` (pairs
    of name and unit) and check the answers. Returns the result object
    (without its last-line formatting)."""
    import jax

    dev = jax.devices()[0]
    compiles = []

    def on_event(event, dur, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            compiles.append(dur)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run_cell(cfg, traffic, metrics, seed, seconds, trace, limit,
                         t_start, weight_bits, peaks, dev, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@contextlib.contextmanager
def served(cfg, traffic, seed: int, weight_bits=None):
    """Deploy ``cfg``'s plan over weights, masks and images made from
    ``seed``: a ``CloudServer`` and the traffic's socket edge sessions in
    threads of this process, every edge warmed by one request and then all
    of them by ``warm_s`` seconds of the traffic itself. Yields a namespace
    of the weights, masks, request images, sessions and the server's
    batcher; stops every session and the server on exit."""
    from bench import deploy, loadgen, model
    from repro import serving

    t = time.perf_counter()
    params, masks = model.make_weights(cfg, seed)
    pool = model.make_pool(cfg, seed, traffic["pool"])
    log(f"setup: weights and {traffic['pool']} images "
        f"{time.perf_counter() - t:.3f} s")
    order = model.seed_rng(seed, 4).permutation(traffic["pool"])

    def image(i):
        return model.request_image(pool, order, i)

    plan = deploy.build_plan(cfg, params, masks, free_port(), weight_bits)
    log(f"plan: {plan.describe()}")
    log(f"edge GEMM backend: {deploy.edge_backend(plan)}")
    t = time.perf_counter()
    srv = serving.CloudServer(plan, max_clients=None)
    log(f"setup: cloud server {time.perf_counter() - t:.3f} s")
    sessions = []
    try:
        t = time.perf_counter()
        for e in range(traffic["edges"]):
            sess = serving.connect(plan, backend="socket")
            sessions.append(sess)
            sess.infer(image(-1 - e))
        log(f"setup: {len(sessions)} edge sessions, one request each "
            f"{time.perf_counter() - t:.3f} s")
        warm_s = traffic["warm_s"]
        warm = loadgen.start(sessions, traffic, image, warm_s,
                             model.seed_rng(seed, 5),
                             first_id=-1 - traffic["edges"], step=-1)
        warm.join(warm_s + 120)
        if warm.failed:
            raise RuntimeError(f"warm-up failed: {warm.failed[0].error}")
        yield types.SimpleNamespace(params=params, masks=masks, image=image,
                                    sessions=sessions,
                                    batcher=deploy.find_batcher())
    finally:
        for sess in sessions:
            sess.close()
        srv.stop()
        del sessions, srv, plan
        gc.collect()


def compare(cfg, params, masks, image, run, seed: int) -> float:
    """Widest gap between a served logit and the reference's, as a share of
    the reference's largest |logit|, over a seeded sample of the requests
    the window answered (``inf`` where nothing can be compared)."""
    import numpy as np

    from bench import model, reference
    fin = {r.rid: r for r in run.finished}
    if not fin:
        return float("inf")
    rng = model.seed_rng(seed, 3)
    pick = rng.choice(sorted(fin), size=min(SAMPLE, len(fin)), replace=False)
    imgs = np.concatenate([image(int(i)) for i in pick])
    t = time.perf_counter()
    ref = reference.logits(cfg, params, masks, imgs, block=REF_BLOCK)
    served_ = np.stack([fin[int(i)].logits for i in pick])
    scale = float(np.max(np.abs(ref)))
    log(f"reference: {len(pick)} requests in {time.perf_counter() - t:.3f} "
        f"s, max |logit| {scale:.6g}")
    if served_.shape != ref.shape or not np.all(np.isfinite(served_)):
        return float("inf")
    return float(np.max(np.abs(served_ - ref)) / scale)


def _run_cell(cfg, traffic, metrics, seed, seconds, trace, limit, t_start,
              weight_bits, peaks, dev, compiles):
    import jax

    from bench import deploy, loadgen, model
    from bench import trace as tracefile

    with served(cfg, traffic, seed, weight_bits) as cell:
        lane0 = deploy.lane_totals(cell.batcher)
        # set-up's objects live as long as the server: keep the collector
        # from rescanning them inside the window, as a warmed server would
        gc.collect()
        gc.freeze()
        pauses = []
        gc.callbacks.append(gc_timer(pauses))
        setup_s = time.perf_counter() - t_start
        n_compiles0 = len(compiles)
        try:
            run = loadgen.start(cell.sessions, traffic, cell.image, seconds,
                                model.seed_rng(seed, 2))
            t_end, lane1 = None, None
            if trace:
                t_end, lane1, trace_path = traced_window(run, cell.batcher)
            run.join(seconds + 120)
        finally:
            gc.callbacks.pop()
            gc.unfreeze()
        window_compiles = len(compiles) - n_compiles0
        lane1 = lane1 or deploy.lane_totals(cell.batcher)
        stats = dev.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        params, masks, image = cell.params, cell.masks, cell.image

    fin = run.finished
    attempted = len(run.requests)
    failed = attempted - len(fin)
    for r in run.failed[:3]:
        log(f"failed request {r.rid}: {r.error}")
    log(f"window: {attempted} requests, {len(fin)} answered, "
        f"{window_compiles} compiles, {len(pauses)} collections (longest "
        f"{max(pauses, default=0) * 1e3:.3f} ms)")
    log("generator lateness (start - due): " + ", ".join(
        f"{k} {v:.6g}" for k, v in loadgen.lateness(run).items()))

    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": memory_peak}}
    rec = {"cfg": cfg, "traffic": traffic, "peaks": peaks,
           "setup_s": setup_s, "t0": run.t0, "trace": None,
           "finished": [r for r in fin if t_end is None or r.done <= t_end],
           "lane": {k: lane1[k] - lane0[k] for k in lane1}}
    if trace:
        tr = tracefile.read(trace_path)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        result["device"]["busy_s"] = tr.busy_s
        result["device"]["window_s"] = tr.window_s
        rec["trace"] = tr
    for name, unit in metrics:
        value = reader(name).read(rec)
        if value is not None:
            result["metrics"][name] = {"value": value, "unit": unit}
        log(f"metric {name}: {value}")
    if trace:
        result["breakdown"] = tracefile.breakdown(rec["trace"])

    # the comparison, once the program's state is freed
    err = compare(cfg, params, masks, image, run, seed)
    checks = {"logit_err": {"value": err, "limit": limit},
              "failed": {"value": failed, "limit": 0}}
    result["correct"] = bool(attempted > 0 and failed == 0
                             and limit is not None and err <= limit)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']})")
    return result


def gc_timer(pauses: list):
    """A ``gc.callbacks`` entry that appends each collection's seconds."""
    start = []

    def cb(phase, info):
        if phase == "start":
            start.append(time.perf_counter())
        elif start:
            pauses.append(time.perf_counter() - start.pop())
    return cb


def traced_window(run, batcher):
    """Trace the end of the running window: half of it (at most 3 s),
    ending a quarter of it (at most 1 s) before it closes. Returns the
    host clock and the batcher's counters at the trace's start, and the
    ``.xplane.pb``."""
    import jax

    from bench import deploy
    from bench import trace as tracefile
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    seconds = run.seconds
    span = min(3.0, seconds / 2)
    lead = seconds - span - min(1.0, seconds / 4)
    time.sleep(max(0.0, run.t0 + lead - time.perf_counter()))
    t_start, lane = time.perf_counter(), deploy.lane_totals(batcher)
    jax.profiler.start_trace(TRACE_DIR,
                             profiler_options=tracefile.options())
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        time.sleep(span)
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    return t_start, lane, paths[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        ap.error(f"unknown workload {args.workload!r}; known: "
                 f"{sorted(cells)}")
    cell = cells[args.workload]

    try:
        from repro import compile_cache
    except ImportError as e:
        log(f"bench: the program (src/repro) is not in this checkout: {e}")
        return 1
    cache = compile_cache.enable()

    try:
        devs = check_chip(cell["chips"])
    except NoChip as e:
        log(f"bench: no chip: {e}")
        return 2
    from bench import model, peaks
    peak = peaks.peaks_for(devs[0].device_kind)
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache {cache}")

    cfg = model.load_config(cell["config"])
    traffic = load_traffic(cell["traffic"])
    e2e, per = cell_metrics(bench, args.workload)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    result = run_cell(cfg, traffic,
                      [(m, units[m]) for m in (per if args.trace else e2e)],
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace),
                      limit=cfg["limits"].get("logit_err"),
                      t_start=T_START, peaks=peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
