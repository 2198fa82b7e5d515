import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# Multi-pod dry-run: lower + compile every (architecture x input shape) on
# the production meshes, with zero real allocation (ShapeDtypeStruct inputs).
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
#         --shape train_4k --mesh pod                    # 16x16 single pod
#     PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
#
# Each run writes experiments/dryrun/<arch>_<shape>_<mesh>.json with
# memory_analysis, cost_analysis, per-collective byte counts, and the three
# roofline terms. Failures (sharding mismatch, OOM at compile, unsupported
# collective) are bugs in the system — the matrix must be green.
#
# NOTE: the os lines above MUST stay the first statements — jax locks
# the device count on first init. The dry run compiles on 512 host
# devices, so it pins itself to the CPU backend and never takes a chip.

import argparse
import json
import time
import traceback
from typing import Optional

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, input_specs, mode_of, supported
from repro.launch.steps import make_decode_step, make_prefill_step, \
    make_train_step
from repro.models import transformer as tr
from repro.optim import adamw, constant
from repro.roofline import hw
from repro.roofline.analysis import model_flops, terms_from_compiled
from repro.sharding.specs import (batch_specs, cache_specs, mesh_axes,
                                  opt_state_specs, param_specs, to_shardings)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def active_params(cfg, params_tree) -> int:
    """Parameter count active per token (MoE: top_k+shared of the experts)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_tree)[0]:
        from repro.sharding.specs import path_keys
        keys = list(path_keys(path))
        n = int(np.prod(leaf.shape))
        if cfg.moe is not None and "moe" in keys and keys[-1] in (
                "w_up", "w_down", "w_gate"):
            n = n * cfg.moe.top_k // cfg.moe.num_experts
        total += n
    return total


def memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        keys = ["argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes"]
        out = {}
        for k in keys:
            if hasattr(ma, k):
                out[k] = int(getattr(ma, k))
        return out or {"repr": str(ma)}
    except Exception as e:                                    # noqa: BLE001
        return {"error": str(e)}


def analytic_memory(cfg, specs, mesh, mode) -> dict:
    """Per-device resident bytes from shardings (params/opt/cache/batch)."""
    from repro.sharding.specs import param_specs as ps
    n_dev = mesh.devices.size

    def tree_bytes(tree):
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(tree))

    params_b = tree_bytes(specs["params"])
    out = {"params_global": params_b, "params_per_device": params_b // n_dev}
    if mode == "train":
        out["opt_state_global"] = 2 * params_b     # m+v same dtypes
        out["batch_global"] = tree_bytes(specs["batch"])
    elif mode == "decode":
        cache_b = tree_bytes(specs["cache"])
        out["cache_global"] = cache_b
        out["cache_per_device"] = cache_b // n_dev
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str = OUT_DIR, save_hlo: bool = False,
            opt_moment_dtype: Optional[str] = None,
            cfg_overrides: Optional[dict] = None,
            grad_accum: int = 1) -> dict:
    # unrolled layers + unrolled attention blocks: HloCostAnalysis counts
    # while bodies once, so roofline numbers need straight-line HLO
    overrides = dict(scan_layers=False, attn_block_unroll=True)
    overrides.update(cfg_overrides or {})
    cfg = get_config(arch).replace(**overrides)
    mesh_name = "multipod" if multi_pod else "pod"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "grad_accum": grad_accum,
           "chips": hw.MULTI_POD_CHIPS if multi_pod else hw.SINGLE_POD_CHIPS}
    ok, why = supported(cfg, shape_name)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mode = mode_of(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(cfg, shape_name)
    t0 = time.time()

    import jax.numpy as jnp
    moment_dtype = jnp.bfloat16 if (
        opt_moment_dtype == "bfloat16"
        or (opt_moment_dtype is None and cfg.d_model >= 7168)) else jnp.float32

    with mesh:
        pspecs = param_specs(specs["params"], cfg, mesh)
        pshard = to_shardings(pspecs, mesh)
        if mode == "train":
            optimizer = adamw(constant(1e-4), moment_dtype=moment_dtype)
            opt_sds = jax.eval_shape(optimizer.init, specs["params"])
            ospecs = opt_state_specs(opt_sds, pspecs)
            bspecs = batch_specs(specs["batch"], cfg, mesh)
            step = make_train_step(cfg, optimizer, grad_accum=grad_accum)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, to_shardings(ospecs, mesh),
                              to_shardings(bspecs, mesh)),
                donate_argnums=(0, 1))
            lowered = jitted.lower(specs["params"], opt_sds, specs["batch"])
        elif mode == "prefill":
            S, B = SHAPES[shape_name]
            bspecs = batch_specs(specs["batch"], cfg, mesh)
            step = make_prefill_step(cfg, max_len=S)
            jitted = jax.jit(
                step, in_shardings=(pshard, to_shardings(bspecs, mesh)))
            lowered = jitted.lower(specs["params"], specs["batch"])
        else:
            cspecs = cache_specs(specs["cache"], cfg, mesh)
            step = make_decode_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, to_shardings(cspecs, mesh), None),
                donate_argnums=(1,))
            lowered = jitted.lower(specs["params"], specs["cache"],
                                   specs["tokens"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = memory_analysis_dict(compiled)
    print(f"[{arch} {shape_name} {mesh_name}] memory_analysis:", mem)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    print(f"[{arch} {shape_name} {mesh_name}] cost_analysis: "
          f"flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")
    hlo = compiled.as_text()
    terms, coll = terms_from_compiled(compiled, rec["chips"], hlo_text=hlo)

    n_total = tr.param_count(specs["params"])
    n_active = active_params(cfg, specs["params"])
    mf = model_flops(cfg, shape_name, n_params_active=n_active)

    rec.update({
        "status": "ok",
        "scan_counted": bool(cfg.scan_layers),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "analytic_memory": analytic_memory(cfg, specs, mesh, mode),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "collectives": {"bytes_by_op": coll.bytes_by_op,
                        "count_by_op": coll.count_by_op},
        "roofline": terms.as_dict(),
        "params_total": n_total,
        "params_active": n_active,
        "model_flops": mf,
        "useful_flops_ratio": (mf / terms.flops_global)
            if terms.flops else None,
        "moment_dtype": str(moment_dtype.__name__) if mode == "train" else None,
    })
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    if save_hlo:
        with open(fn.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def run_split_serve(arch: str, out_dir: str = OUT_DIR,
                    num_microbatches: int = 8,
                    seq_len: int = 4096, batch: int = 32,
                    cfg_overrides: Optional[dict] = None) -> dict:
    """Tier-B pod-split serving dry-run: lower + compile the 2-pod
    microbatch pipeline (core/partition/pod_pipeline) on the multi-pod
    mesh and extract the T_TX term (collective-permute bytes crossing the
    pod boundary) for comparison against the Eq. 5 latency model."""
    import jax.numpy as jnp

    from repro.core.partition import pod_pipeline as pp
    from repro.core.partition.latency_model import (split_latency,
                                                    transformer_layer_costs)
    from repro.core.partition.profiles import TPU_TWO_POD

    cfg = get_config(arch).replace(scan_layers=False,
                                   **(cfg_overrides or {}))
    assert pp.pipeline_supported(cfg), arch
    n_pods = 2
    mesh = make_production_mesh(multi_pod=True)
    rec = {"arch": arch, "mode": "split_serve", "mesh": "multipod",
           "chips": hw.MULTI_POD_CHIPS, "num_microbatches": num_microbatches,
           "seq_len": seq_len, "batch": batch}
    params = jax.eval_shape(
        lambda: tr.init_params(cfg, jax.random.PRNGKey(0)))
    sp = dict(params)
    sp["runs"] = [jax.eval_shape(
        lambda p: pp.stack_stage_params(p, cfg, n_pods), params)]
    batch_in = {"tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)}
    if cfg.embeds_input:
        batch_in = {"embeds": jax.ShapeDtypeStruct(
            (batch, seq_len, cfg.d_model), jnp.dtype(cfg.dtype))}

    t0 = time.time()
    with mesh:
        from jax.sharding import PartitionSpec as P
        pspecs = param_specs(sp, cfg, mesh)

        # the stacked stage dim shards over "pod"; inner dims must then
        # drop "pod" from any composite ("pod","data") data-axis entry
        def _stage_spec(spec):
            # specs were computed on the already-stacked tree; dim 0 is the
            # stage dim (always unsharded by the name rules) -> "pod"
            inner = []
            for e in tuple(spec):
                if isinstance(e, tuple) and "pod" in e:
                    rest = tuple(a for a in e if a != "pod")
                    inner.append(rest[0] if len(rest) == 1 else
                                 (rest or None))
                else:
                    inner.append(e)
            assert not inner or inner[0] is None, spec
            return P(*(("pod",) + tuple(inner[1:])))

        pspecs["runs"] = [jax.tree_util.tree_map(
            _stage_spec, pspecs["runs"][0],
            is_leaf=lambda x: isinstance(x, P))]
        step = pp.make_split_serve_step(cfg, n_pods, num_microbatches, mesh)
        lowered = jax.jit(step, in_shardings=(
            to_shardings(pspecs, mesh), None)).lower(sp, batch_in)
        compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    hlo = compiled.as_text()
    terms, coll = terms_from_compiled(compiled, rec["chips"], hlo_text=hlo)
    rec["memory_analysis"] = memory_analysis_dict(compiled)
    rec["collectives"] = {"bytes_by_op": coll.bytes_by_op,
                          "count_by_op": coll.count_by_op}
    rec["roofline"] = terms.as_dict()
    # Eq. 5 prediction for the same split (layer c = L/2)
    costs = transformer_layer_costs(cfg, seq_len)
    pred = split_latency(costs, cfg.num_layers // 2, TPU_TWO_POD,
                         seq_len * cfg.d_model * 2)
    # per-request boundary bytes: activation (B/M, S, d) x M microbatches
    rec["eq5_prediction"] = {k: v * batch for k, v in pred.items()
                             if k.startswith("T")}
    rec["boundary_bytes_model"] = batch * seq_len * cfg.d_model * 2
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}_split_serve_multipod.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[{arch} split_serve] compile={rec['compile_s']}s "
          f"ppermute_bytes="
          f"{coll.bytes_by_op.get('collective-permute', 0):.3e} "
          f"model_boundary_bytes={rec['boundary_bytes_model']:.3e}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--split-serve", action="store_true",
                    help="Tier-B pod-split pipeline dry-run (multipod)")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--scan", action="store_true",
                    help="lower with scan-over-layers (fallback for "
                         "compiles too big to unroll on this host; "
                         "cost_analysis counts the loop body once — "
                         "recorded as scan_counted)")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    if args.split_serve:
        run_split_serve(args.arch, args.out)
        return

    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    pairs = ([(a, s) for a in ARCH_IDS for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    failures = []
    for arch, shape in pairs:
        for mp in meshes:
            try:
                over = ({"scan_layers": True, "attn_block_unroll": False}
                        if args.scan else None)
                rec = run_one(arch, shape, mp, args.out,
                              save_hlo=args.save_hlo, cfg_overrides=over)
                status = rec["status"]
                extra = (f" compile={rec.get('compile_s')}s "
                         f"dominant={rec.get('roofline', {}).get('dominant')}"
                         if status == "ok" else f" ({rec.get('reason')})")
                print(f"== {arch} {shape} "
                      f"{'multipod' if mp else 'pod'}: {status}{extra}")
            except Exception:                                 # noqa: BLE001
                failures.append((arch, shape, mp))
                print(f"== {arch} {shape} {'multipod' if mp else 'pod'}: "
                      f"FAILED")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"dry-run failures: {failures}")


if __name__ == "__main__":
    main()
