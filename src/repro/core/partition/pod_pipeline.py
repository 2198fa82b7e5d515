"""Tier-B split inference: the paper's edge/cloud partition mapped onto the
multi-pod mesh (DESIGN.md §2).

The split point ``c`` becomes a pod-boundary partition: pod p holds layers
[p*L/P, (p+1)*L/P); the boundary activation crosses pods as a
``jax.lax.ppermute`` over the (slow) inter-pod links — the TPU analogue of
the paper's wireless hop, and the T_TX term of Eq. 5 (visible in the
dry-run HLO as collective-permute bytes).

Execution is the SPMD microbatch pipeline (GPipe-style, collective-permute
formulation): requests are split into ``num_microbatches``; each pipeline
tick every pod runs its local stage on its current activation, then the
activation shifts one pod to the right. Ticks = microbatches + pods - 1
(fill + drain). Steady-state utilization = M / (M + P - 1).

``shard_map(axis_names={"pod"})`` makes only the pod axis manual: inside a
stage the layers still shard over ("data", "model") exactly as the
non-split model does (GSPMD auto axes).

Scope: architectures whose layer stack is a single homogeneous run
(dense GQA, pure-MoE, pure-SSM — 8 of the 10 assigned archs; zamba2's
shared-block hybrid and deepseek's dense-head+moe mix stay on the Tier-A
layer-range executor) and num_layers % n_pods == 0.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as tr


def pipeline_supported(cfg: ModelConfig) -> bool:
    runs = tr.layer_runs(cfg)
    return (len(runs) == 1 and not cfg.shared_attn_period
            and runs[0].kind in ("attn", "moe", "ssm"))


def stack_stage_params(params: Dict[str, Any], cfg: ModelConfig,
                       n_stages: int):
    """Restack the single run's (L, ...) weights into (n_stages, L/n, ...).

    The leading stage dim is the one the "pod" mesh axis shards — that is
    what gives each pod residency of ONLY its own layer range.
    """
    assert pipeline_supported(cfg), "single homogeneous run required"
    L = cfg.num_layers
    assert L % n_stages == 0, (L, n_stages)
    run = params["runs"][0]
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n_stages, L // n_stages) + a.shape[1:]), run)


def _stage_apply(cfg: ModelConfig, stage_params, x, angles,
                 shard_acts: bool = True):
    """Run this pod's layer range over x (local microbatch).

    ``shard_acts`` keeps the microbatch activation sequence-sharded over
    "data" inside the (pod-manual) stage, so the boundary ppermute moves
    1/256th of the activation per chip instead of a full replica
    (EXPERIMENTS.md §Perf-3). Sequence (not batch) because the microbatch
    dim is already small (B/M can be < |data|).
    """
    kind = tr.layer_runs(cfg)[0].kind

    def cstr(h):
        if not shard_acts:
            return h
        return jax.lax.with_sharding_constraint(h, P(None, "data", "model"))

    x = cstr(x)

    def body(h, lp):
        if kind == "attn":
            h, _ = tr._attn_block(cfg, lp, h, angles, None)
        elif kind == "moe":
            h, _, _ = tr._moe_block(cfg, lp, h, angles, None)
        else:
            h, _ = tr._ssm_block(cfg, lp, h, None)
        return cstr(h), None

    h, _ = jax.lax.scan(body, x, stage_params)
    return h


def make_pipeline_forward(cfg: ModelConfig, n_pods: int,
                          num_microbatches: int, mesh):
    """Returns fn(stage_params, x, angles) -> y.

    x (B, S, d_model) hidden states (embedding/lm_head run outside — they
    are data-parallel); y (B, S, d_model) after all L layers.
    B % num_microbatches == 0.
    """
    def pipelined(stage_params, x, angles):
        # stage_params leaves: (1, L/P, ...) local slices  (pod manual axis)
        local = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        B = x.shape[0]
        M = num_microbatches
        mb = x.reshape((M, B // M) + x.shape[1:])
        # angles ride along with their microbatch (per-row M-RoPE safe)
        amb = angles.reshape((M, B // M) + angles.shape[1:])
        pod = jax.lax.axis_index("pod")
        ticks = M + n_pods - 1
        state = jnp.zeros_like(mb[0])
        state_a = jnp.zeros_like(amb[0])
        outs = jnp.zeros_like(mb)

        def tick(carry, t):
            state, state_a, outs = carry
            sel = jnp.minimum(t, M - 1)
            inject = jnp.where(t < M, mb[sel], jnp.zeros_like(mb[0]))
            inject_a = jnp.where(t < M, amb[sel], jnp.zeros_like(amb[0]))
            x_in = jnp.where(pod == 0, inject, state)
            a_in = jnp.where(pod == 0, inject_a, state_a)
            h = _stage_apply(cfg, local, x_in, a_in)
            # shift one pod to the right (the paper's T_TX hop)
            shift = [(p, p + 1) for p in range(n_pods - 1)]
            nxt = jax.lax.ppermute(h, "pod", shift)
            nxt_a = jax.lax.ppermute(a_in, "pod", shift)
            # the LAST pod emits microbatch t-(P-1) at tick t
            out_idx = t - (n_pods - 1)
            outs = jnp.where(
                (pod == n_pods - 1) & (out_idx >= 0),
                outs.at[jnp.maximum(out_idx, 0)].set(h), outs)
            return (nxt, nxt_a, outs), None

        (state, state_a, outs), _ = jax.lax.scan(
            tick, (state, state_a, outs), jnp.arange(ticks))
        y = outs.reshape((B,) + x.shape[1:])
        # broadcast the last pod's result to every pod (replicated output).
        # fp32 psum: XLA CPU's AllReducePromotion pass crashes on bf16
        # all-reduce (compiler bug worked around; on TPU this is free).
        y = jax.lax.psum(
            jnp.where(pod == n_pods - 1, y.astype(jnp.float32),
                      jnp.zeros(y.shape, jnp.float32)), "pod")
        return y.astype(x.dtype)

    return jax.shard_map(pipelined, mesh=mesh, axis_names={"pod"},
                         in_specs=(P("pod"), P(), P()), out_specs=P(),
                         check_vma=False)


def make_split_serve_step(cfg: ModelConfig, n_pods: int,
                          num_microbatches: int, mesh):
    """Full request step: embed -> pod-pipelined stack -> final norm/head.

    Returns fn(params_with_stacked_runs, batch) -> last-position logits.
    ``params`` as from init_params but with params['runs'][0] restacked by
    stack_stage_params (leading (n_pods, L/P) dims).
    """
    pipe = make_pipeline_forward(cfg, n_pods, num_microbatches, mesh)

    def step(params, batch):
        x, B, S = tr.embed_inputs(params, cfg, batch)
        angles = tr._angles_for(cfg, batch, B, S)
        if angles is None:
            angles = jnp.zeros((B, S, max(cfg.head_dim // 2, 1)),
                               jnp.float32)
        y = pipe(params["runs"][0], x, angles)
        y = tr.rmsnorm(y, params["final_norm"], cfg.norm_eps)
        return tr._lm_logits(params, cfg, y[:, -1])

    return step
