"""Smoke check: the paper's AlexNet-224 served over the socket path on one
TPU chip, with the edge half through the compiled Pallas GEMM.

    python chip_smoke.py [--seed 0]

One process, one chip. A ``CloudServer`` and four socket edge sessions run
in threads of this process. The deployment is AlexNet-224 (38 classes) at
its published widths with random weights from ``--seed``, every conv after
the first pruned to half its channels and compacted, int8 per-channel
edge weights through ``masked_matmul``, the int8 wire codec, split after
pool5 (c = 13), and cross-edge dynamic batching (8 rows, 3 ms window).

Each edge sends 8 synthetic 224-px PlantVillage images through the
pipelined ``infer_many`` (the batcher fuses rows across edges) and then
the same 8 one at a time through ``infer`` (the latency pass). The script
fails unless JAX runs on a TPU, the edge resolves to the compiled Pallas
kernel, its split-boundary features match the XLA ref GEMM's, no request
saw a fault, every batch succeeded and some fused several rows, and the
served logits match a float32 reference, each within the tolerance it
prints first. The timings it prints are smoke figures, not
benchmark numbers. The last line of its output is one JSON object naming
the device; on any failure it exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import socket
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SPLIT = 13            # after pool5: the edge runs all five convs
EDGES = 4
PER_EDGE = 8
#: served logits (int8 weights, int8 wire, the chip's default matmul
#: precision) against the float32 reference: max |dlogit| as a share of
#: the reference's largest |logit|
REF_RTOL = 0.1
#: the compiled-kernel edge's split-boundary features against the XLA
#: ``ref`` GEMM edge over the same int8 weights, as a share of the largest
#: |feature|: both sum the same float32 products, in another order (K in
#: 128-wide slices in the kernel), ~K * eps apart; a broken tile or mask
#: is off by O(1)
KERNEL_RTOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_edges(sessions, images, call):
    """Run ``call(session, its images)`` on one thread per edge, all
    released together; -> (per-edge results, wall seconds)."""
    out = [None] * len(sessions)
    errors = []
    gate = threading.Barrier(len(sessions) + 1)

    def edge(e):
        gate.wait()
        try:
            out[e] = call(sessions[e], images[e * PER_EDGE:(e + 1) * PER_EDGE])
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=edge, args=(e,))
               for e in range(len(sessions))]
    for t in threads:
        t.start()
    gate.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return out, wall


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the images")
    args = ap.parse_args()

    try:
        from repro import compile_cache
    except ImportError as e:
        fail(f"the repository's src/ is not beside this script ({e})")
    cache_dir = compile_cache.enable()

    import jax
    import numpy as np

    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        fail(f"no TPU: JAX found no device ({e})")
    if dev.platform != "tpu":
        fail(f"no TPU: JAX runs on {dev.platform!r} ({dev.device_kind}); "
             f"this check runs only on a TPU chip")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device['kind']} x{device['count']} "
          f"(compile cache {cache_dir})", flush=True)

    from repro import serving
    from repro.configs.alexnet_plantvillage import CONFIG as cfg
    from repro.core.collab.faults import fault_record
    from repro.core.collab.quant import resolve_backend
    from repro.core.collab.runtime import SplitFnBank
    from repro.core.pruning.masks import cnn_masks_from_ratios
    from repro.data.synthetic import PlantVillageSynthetic
    from repro.models.cnn import cnn_apply, init_cnn_params

    t = time.perf_counter()
    params = init_cnn_params(jax.random.PRNGKey(args.seed), cfg)
    masks = cnn_masks_from_ratios(
        params, cfg, {i: 0.5 for i, s in enumerate(cfg.layers)
                      if s.kind == "conv" and i > 0})
    plan = serving.DeploymentPlan.from_args(
        params, cfg, SPLIT, masks=masks, compact=True, codec="int8",
        port=free_port(), shape_link=False,
        quant=serving.QuantPolicy(weight_bits=8),
        batching=serving.BatchingPolicy(max_batch=8, max_wait_ms=3.0))
    data = PlantVillageSynthetic(n_per_class=5, hw=224, seed=args.seed)
    images = [b["image"] for b in
              itertools.islice(data.test_batches(1), EDGES * PER_EDGE)]
    print(f"plan: {plan.describe()}", flush=True)
    print(f"setup: {time.perf_counter() - t:.3f} s (weights, masks, "
          f"{len(images)} images {images[0].shape[1:]})", flush=True)

    backend = resolve_backend(plan.quant)
    print(f"edge quant backend: {backend}", flush=True)
    if backend != ("pallas", False):
        fail(f"edge GEMM resolved to {backend}, not the compiled Pallas "
             f"kernel ('pallas', False)")

    # float32 reference: the dense masked network on the host CPU
    cpu = jax.devices("cpu")[0]
    t = time.perf_counter()
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, x: cnn_apply(p, cfg, x, masks=plan.masks))(
                jax.device_put(params, cpu), np.concatenate(images)))
    scale = float(np.max(np.abs(ref)))
    print(f"reference: float32 dense masked cnn_apply, precision HIGHEST, "
          f"on {cpu.device_kind}: {time.perf_counter() - t:.3f} s, "
          f"max |logit| {scale:.6g}", flush=True)
    print(f"tolerance: served vs reference max |dlogit| <= {REF_RTOL} * "
          f"{scale:.6g} = {REF_RTOL * scale:.6g}", flush=True)

    # the kernel alone, before the int8 wire codec rounds its error away
    feats = {}
    for name, quant in (("kernel", plan.quant),
                        ("ref", serving.QuantPolicy(weight_bits=8,
                                                    backend="ref"))):
        edge = SplitFnBank(params, cfg, plan.masks, compact=True,
                           quant=quant).get(SPLIT)[0]
        feats[name] = np.concatenate([np.asarray(edge(im)) for im in images])
        if name == "kernel":
            # the bank's jitted step, bound to its device-resident params
            hlo = edge.func.lower(*edge.args, images[0]).compile().as_text()
            n_kernels = hlo.count("tpu_custom_call")
            n_convs = sum(s.kind == "conv" for s in cfg.layers[:SPLIT])
            print(f"edge executable: {n_kernels} Pallas kernel calls for "
                  f"{n_convs} conv layers", flush=True)
            if n_kernels != n_convs:
                fail("the edge executable does not run one compiled "
                     "masked_matmul per conv layer")
    fscale = float(np.max(np.abs(feats["ref"])))
    print(f"tolerance: kernel edge vs ref-GEMM edge max |dfeature| <= "
          f"{KERNEL_RTOL} * {fscale:.6g} = {KERNEL_RTOL * fscale:.6g}",
          flush=True)
    d_kernel = float(np.max(np.abs(feats["kernel"] - feats["ref"])))
    print(f"edge features {feats['ref'].shape}: max |dfeature| kernel vs "
          f"ref GEMM {d_kernel:.6g}", flush=True)
    if not d_kernel <= KERNEL_RTOL * fscale:
        fail("compiled kernel edge off the ref-GEMM edge")

    t = time.perf_counter()
    srv = serving.CloudServer(plan, max_clients=None)
    print(f"cloud warm-up (quantize + compile c={SPLIT} x buckets "
          f"{plan.batching.resolved_buckets}): "
          f"{time.perf_counter() - t:.3f} s", flush=True)
    sessions = []
    try:
        for e in range(EDGES):
            t = time.perf_counter()
            sess = serving.connect(plan, backend="socket")
            sessions.append(sess)
            sess.infer(images[e * PER_EDGE])
            print(f"edge {e} warm-up (quantize + compile + 1 request): "
                  f"{time.perf_counter() - t:.3f} s", flush=True)

        piped, wall = run_edges(sessions, images,
                                lambda s, ims: s.infer_many(ims))
        print(f"smoke req/s (pipelined infer_many, {EDGES} edges x "
              f"{PER_EDGE}): {EDGES * PER_EDGE / wall:.6g} "
              f"({wall:.6f} s)", flush=True)
        synced, _ = run_edges(sessions, images,
                              lambda s, ims: [s.infer(im) for im in ims])
        lat = [r["t_total"] for rs in synced for r in rs]
        print(f"smoke p50 (synchronous infer, {EDGES} edges): "
              f"{statistics.median(lat) * 1e3:.6g} ms", flush=True)
    finally:
        for sess in sessions:
            sess.close()
        srv.stop()

    results = {"pipelined": piped, "synchronous": synced}
    for name, per_edge in results.items():
        for e, rs in enumerate(per_edge):
            for r in rs:
                if r["fault"] != fault_record():
                    fail(f"{name} edge {e}: fault record {r['fault']}")
    stats = list(srv.batch_stats.values())
    sizes = [b for st in stats for b in st["batch_sizes"]]
    print(f"batches: {sum(st['batches'] for st in stats)} calls, "
          f"{sum(st['rows'] for st in stats)} rows, recent sizes {sizes}, "
          f"failed rows {sum(st['failed_rows'] for st in stats)}",
          flush=True)
    if not stats or any(st["failed_rows"] for st in stats):
        fail(f"batcher stats {srv.batch_stats}")
    if max(sizes, default=0) < 2:
        fail("no batch fused more than one row")

    for name, per_edge in results.items():
        served = np.concatenate([r["logits"] for rs in per_edge for r in rs])
        if served.shape != ref.shape or not np.all(np.isfinite(served)):
            fail(f"{name}: logits {served.shape}, finite "
                 f"{bool(np.all(np.isfinite(served)))}")
        d_ref = float(np.max(np.abs(served - ref)))
        print(f"{name}: max |dlogit| vs float32 reference {d_ref:.6g} "
              f"(<= {REF_RTOL * scale:.6g})", flush=True)
        if not d_ref <= REF_RTOL * scale:
            fail(f"{name}: served logits off the float32 reference")

    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
