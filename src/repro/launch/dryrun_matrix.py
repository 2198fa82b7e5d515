"""Resumable driver for the full dry-run matrix.

Runs every (arch x shape x mesh) combination as a SUBPROCESS (so a single
giant compile cannot take down the sweep), smallest-estimated-cost first,
skipping pairs whose JSON already exists. Each subprocess is
``python -m repro.launch.dryrun --arch A --shape S --mesh M``.

    PYTHONPATH=src python -m repro.launch.dryrun_matrix [--mesh pod|multipod|both]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# a host-device compile sweep: neither this process nor its children (which
# inherit the variable) may take an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.specs import SHAPES, mode_of, supported

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def est_cost(arch: str, shape: str) -> float:
    """Rough compile-cost order: unrolled instruction count proxy."""
    cfg = get_config(arch)
    per_layer = cfg.d_model / 1024
    if cfg.moe is not None:
        per_layer *= 1 + cfg.moe.num_experts / 16
    mode = mode_of(shape)
    S, B = SHAPES[shape]
    tok = {"train": 3.0 * S * B, "prefill": S * B, "decode": B}[mode]
    return cfg.num_layers * per_layer * (1 + tok / 2**20)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--timeout", type=int, default=2100)
    ap.add_argument("--scan-fallback", action="store_true", default=True)
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()
    meshes = {"pod": ["pod"], "multipod": ["multipod"],
              "both": ["pod", "multipod"]}[args.mesh]

    todo = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            ok, why = supported(cfg, shape)
            if not ok:
                continue
            for mesh in meshes:
                fn = os.path.join(args.out, f"{arch}_{shape}_{mesh}.json")
                if os.path.exists(fn):
                    try:
                        if json.load(open(fn)).get("status") == "ok":
                            continue
                    except Exception:          # noqa: BLE001
                        pass
                todo.append((est_cost(arch, shape), arch, shape, mesh))
    # single-pod first (roofline baseline), then multipod
    todo.sort(key=lambda t: (t[3] != "pod", t[0]))
    print(f"{len(todo)} runs queued", flush=True)
    failures = []
    for cost, arch, shape, mesh in todo:
        t0 = time.time()
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mesh", mesh, "--out", args.out]
        print(f">>> {arch} {shape} {mesh} (est {cost:.0f})", flush=True)
        try:
            r = subprocess.run(cmd, timeout=args.timeout,
                               capture_output=True, text=True)
            status = "ok" if r.returncode == 0 else "FAIL"
            if status == "FAIL":
                print(r.stdout[-1500:], r.stderr[-3000:], flush=True)
        except subprocess.TimeoutExpired:
            status = "TIMEOUT"
        if status != "ok" and args.scan_fallback:
            print(f"    retrying {arch} {shape} {mesh} with --scan",
                  flush=True)
            try:
                r = subprocess.run(cmd + ["--scan"],
                                   timeout=args.timeout,
                                   capture_output=True, text=True)
                status = ("ok(scan)" if r.returncode == 0
                          else "FAIL(scan)")
                if r.returncode != 0:
                    print(r.stdout[-1500:], r.stderr[-3000:], flush=True)
            except subprocess.TimeoutExpired:
                status = "TIMEOUT(scan)"
        if not status.startswith("ok"):
            failures.append((arch, shape, mesh))
        print(f"<<< {arch} {shape} {mesh}: {status} "
              f"({time.time() - t0:.0f}s)", flush=True)
    print("failures:", failures, flush=True)


if __name__ == "__main__":
    main()
