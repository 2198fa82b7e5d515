"""Composable decoder/encoder stack covering every assigned family:

  dense   — pre-norm GQA + (gated/non-gated) FFN           [gemma, qwen*, nemotron]
  moe     — GQA or MLA attention + sort-dispatch MoE FFN   [mixtral, deepseek-v3]
  ssm     — Mamba2 (SSD) blocks, attention-free            [mamba2]
  hybrid  — Mamba2 backbone + one SHARED attention block
            applied every ``shared_attn_period`` layers    [zamba2]
  audio   — bidirectional encoder over precomputed frame
            embeddings (stubbed conv frontend)             [hubert]
  vlm     — dense decoder with M-RoPE; vision patch
            embeddings (stubbed ViT) prefix the text       [qwen2-vl]

Layer stacks are grouped into homogeneous *runs* and executed with
``lax.scan`` over stacked per-layer weights: compile cost is O(1) in depth,
which keeps 96-layer dry-run compiles tractable and the production HLO
small. Hybrid stacks scan over (period)-sized groups — inner scan over the
Mamba2 layers of a group, then the shared attention block — so per-group
shared-KV caches have static shapes.

Three execution entry points, all cache-consistent with each other (tested):
  forward      — full sequence, logits for every position (train)
  prefill      — full sequence, last-position logits + decode-ready cache
  decode_step  — one token against the cache

Pruning integration (the paper's technique): ``masks`` mirrors the runs
structure with per-layer structured masks — attention ``head_mask``, FFN
``ffn_mask``, MoE ``expert_mask``, SSD ``ssm_head_mask``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ssm as ssm_lib
from repro.models.layers.attention import (KVCache, MLACache, gqa_decode,
                                           gqa_forward, init_gqa_params,
                                           init_kv_cache, init_mla_cache,
                                           init_mla_params, mla_decode,
                                           mla_forward)
from repro.models.layers.mlp import init_mlp_params, mlp_forward
from repro.models.layers.moe import init_moe_params, moe_forward
from repro.models.layers.norms import rmsnorm
from repro.models.layers.rope import (mrope_angles, positions_for,
                                      rope_angles, text_mrope_positions)


# ---------------------------------------------------------------------------
# run grouping
# ---------------------------------------------------------------------------
class Run(NamedTuple):
    kind: str      # attn | attn_dense | moe | ssm
    start: int
    count: int


def layer_runs(cfg: ModelConfig) -> List[Run]:
    kinds = cfg.layer_kinds()
    runs: List[Run] = []
    for i, k in enumerate(kinds):
        if runs and runs[-1].kind == k:
            runs[-1] = Run(k, runs[-1].start, runs[-1].count + 1)
        else:
            runs.append(Run(k, i, 1))
    return runs


def hybrid_split(cfg: ModelConfig, count: int) -> Tuple[int, int]:
    """(n_groups, tail) for a hybrid run of ``count`` ssm layers."""
    period = cfg.shared_attn_period
    return count // period, count % period


def _maybe_scan(cfg, body, carry, xs):
    """lax.scan, or an unrolled python loop when cfg.scan_layers=False.

    Unrolling exists for cost analysis: XLA's HloCostAnalysis counts a
    while-loop body once regardless of trip count, so FLOP/byte counts
    must come from straight-line HLO. Results are identical either way
    (tested).
    """
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        inp = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, inp)
        ys.append(y)
    if not ys or all(
            not jax.tree_util.tree_leaves(y) for y in ys):
        # preserve the ys tree structure (all-None) for caller unpacking
        return carry, (ys[0] if ys else None)
    stacked = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
    return carry, stacked


def _group_tree(tree, n_groups: int, period: int):
    main = jax.tree_util.tree_map(
        lambda a: a[:n_groups * period].reshape(
            (n_groups, period) + a.shape[1:]), tree)
    tail = jax.tree_util.tree_map(lambda a: a[n_groups * period:], tree)
    return main, tail


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_attn_layer(cfg: ModelConfig, dtype):
    def init(key):
        ks = jax.random.split(key, 4)
        if cfg.attention == "mla":
            att = init_mla_params(ks[0], cfg, dtype)
        else:
            att = init_gqa_params(ks[0], cfg, dtype)
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": att,
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "mlp": init_mlp_params(ks[1], cfg.d_model, cfg.d_ff,
                                   cfg.activation, dtype),
        }
    return init


def _init_moe_layer(cfg: ModelConfig, dtype):
    def init(key):
        ks = jax.random.split(key, 2)
        if cfg.attention == "mla":
            att = init_mla_params(ks[0], cfg, dtype)
        else:
            att = init_gqa_params(ks[0], cfg, dtype)
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": att,
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "moe": init_moe_params(ks[1], cfg.d_model, cfg.moe,
                                   cfg.activation, dtype),
        }
    return init


def _init_ssm_layer(cfg: ModelConfig, dtype):
    def init(key):
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ssm": ssm_lib.init_ssm_params(key, cfg, dtype),
        }
    return init


_RUN_INIT = {"attn": _init_attn_layer, "attn_dense": _init_attn_layer,
             "moe": _init_moe_layer, "ssm": _init_ssm_layer}


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    V = cfg.padded_vocab
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (V, cfg.d_model), jnp.float32)
                  * 0.02).astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[1], (cfg.d_model, V), jnp.float32)
            / math.sqrt(cfg.d_model)).astype(dtype)
    runs = layer_runs(cfg)
    run_keys = jax.random.split(keys[2], max(len(runs), 1))
    params["runs"] = []
    for r, rk in zip(runs, run_keys):
        layer_keys = jax.random.split(rk, r.count)
        params["runs"].append(jax.vmap(_RUN_INIT[r.kind](cfg, dtype))(layer_keys))
    if cfg.shared_attn_period:
        ks = jax.random.split(keys[3], 2)
        params["shared"] = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": init_gqa_params(ks[0], cfg, dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "mlp": init_mlp_params(ks[1], cfg.d_model,
                                   cfg.d_ff or 4 * cfg.d_model,
                                   cfg.activation, dtype),
        }
    if cfg.mtp_depth:
        ks = jax.random.split(keys[4], 2)
        mtp_cfg = (cfg.replace(attention="gqa") if cfg.attention == "mla"
                   else cfg)
        params["mtp"] = {
            "proj": (jax.random.normal(
                keys[5], (2 * cfg.d_model, cfg.d_model), jnp.float32)
                / math.sqrt(2 * cfg.d_model)).astype(dtype),
            "block": _init_attn_layer(mtp_cfg, dtype)(ks[0]),
            "ln": jnp.ones((cfg.d_model,), dtype),
        }
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# rope angles
# ---------------------------------------------------------------------------
def _rope_dim(cfg: ModelConfig) -> int:
    return (cfg.mla.qk_rope_head_dim if cfg.attention == "mla"
            else cfg.head_dim)


def _angles_for(cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
                B: int, S: int, offset=0):
    if cfg.rope_mode == "none":
        return None
    if cfg.rope_mode == "mrope":
        pos = batch.get("mrope_positions")
        if pos is None:
            pos = text_mrope_positions(B, S, offset)
        return mrope_angles(pos, _rope_dim(cfg), cfg.rope_theta,
                            cfg.mrope_sections)
    pos = positions_for(B, S, offset)
    pos = jnp.broadcast_to(pos, (B, S))
    return rope_angles(pos, _rope_dim(cfg), cfg.rope_theta)


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------
def _attn_block(cfg, lp, x, angles, mask, collect_kv=False):
    head_mask = None if mask is None else mask.get("head_mask")
    ffn_mask = None if mask is None else mask.get("ffn_mask")
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if cfg.attention == "mla":
        a, kv = mla_forward(lp["attn"], cfg, h, angles, head_mask=head_mask)
    else:
        a, kv = gqa_forward(lp["attn"], cfg, h, angles, head_mask=head_mask)
    x = x + a
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    x = x + mlp_forward(lp["mlp"], h, cfg.activation, ffn_mask=ffn_mask)
    return (x, kv) if collect_kv else (x, None)


def _moe_block(cfg, lp, x, angles, mask, collect_kv=False):
    head_mask = None if mask is None else mask.get("head_mask")
    expert_mask = None if mask is None else mask.get("expert_mask")
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if cfg.attention == "mla":
        a, kv = mla_forward(lp["attn"], cfg, h, angles, head_mask=head_mask)
    else:
        a, kv = gqa_forward(lp["attn"], cfg, h, angles, head_mask=head_mask)
    x = x + a
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    m, metrics = moe_forward(lp["moe"], cfg.moe, h, cfg.activation,
                             expert_mask=expert_mask)
    return x + m, metrics, (kv if collect_kv else None)


def _ssm_block(cfg, lp, x, mask, collect_state=False):
    head_mask = None if mask is None else mask.get("ssm_head_mask")
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if collect_state:
        o, st = ssm_lib.ssm_forward(lp["ssm"], cfg, h, head_mask=head_mask,
                                    return_state=True)
        return x + o, st
    return x + ssm_lib.ssm_forward(lp["ssm"], cfg, h, head_mask=head_mask), None


def _shared_block(cfg, sp, x, angles, collect_kv=False):
    h = rmsnorm(x, sp["ln1"], cfg.norm_eps)
    a, kv = gqa_forward(sp["attn"], cfg, h, angles)
    x = x + a
    h = rmsnorm(x, sp["ln2"], cfg.norm_eps)
    x = x + mlp_forward(sp["mlp"], h, cfg.activation)
    return (x, kv) if collect_kv else (x, None)


# ---------------------------------------------------------------------------
# embedding & head
# ---------------------------------------------------------------------------
def embed_inputs(params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, int, int]:
    if cfg.embeds_input:                       # audio: stubbed conv frontend
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        B, S = x.shape[:2]
    elif cfg.vision_tokens:                    # vlm: vision prefix + text
        tok = batch["tokens"]
        B = tok.shape[0]
        emb = params["embed"][tok]
        vis = batch["vision_embeds"].astype(emb.dtype)   # (B, V, d)
        x = jnp.concatenate([vis, emb], axis=1)
        S = x.shape[1]
    else:
        tok = batch["tokens"]
        B, S = tok.shape
        x = params["embed"][tok]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x, B, S


def _lm_logits(params, cfg, x):
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head
    if cfg.logit_softcap:
        cap = cfg.logit_softcap
        logits = jnp.tanh(logits / cap) * cap
    return logits


# ---------------------------------------------------------------------------
# stack walker (shared by forward & prefill)
# ---------------------------------------------------------------------------
def _run_stack(params, cfg: ModelConfig, x, angles, masks, collect: bool):
    """Run all layer runs over x. Returns (x, aux, caches or None)."""
    runs = layer_runs(cfg)
    masks = masks if masks is not None else [None] * len(runs)
    aux = jnp.zeros((), jnp.float32)
    zl = jnp.zeros((), jnp.float32)
    caches: List[Any] = []
    shared_kv = None
    shared = params.get("shared")
    period = cfg.shared_attn_period

    for run, rp, rmask in zip(runs, params["runs"], masks):
        xs = (rp, rmask) if rmask is not None else (rp,)

        def unpack(inp):
            return inp if len(inp) == 2 else (inp[0], None)

        if run.kind in ("attn", "attn_dense"):
            def body(carry, inp):
                lp, mk = unpack(inp)
                h, kv = _attn_block(cfg, lp, carry, angles, mk,
                                    collect_kv=collect)
                return h, kv
            if cfg.remat:
                body = jax.checkpoint(body)
            x, kv = _maybe_scan(cfg, body, x, xs)
            caches.append(kv)
        elif run.kind == "moe":
            def body(carry, inp):
                lp, mk = unpack(inp)
                h, a, z = carry
                h, metrics, kv = _moe_block(cfg, lp, h, angles, mk,
                                            collect_kv=collect)
                return (h, a + metrics.aux_loss, z + metrics.z_loss), kv
            if cfg.remat:
                body = jax.checkpoint(body)
            (x, aux, zl), kv = _maybe_scan(cfg, body, (x, aux, zl), xs)
            caches.append(kv)
        elif run.kind == "ssm" and not period:
            def body(carry, inp):
                lp, mk = unpack(inp)
                h, st = _ssm_block(cfg, lp, carry, mk, collect_state=collect)
                return h, st
            if cfg.remat:
                body = jax.checkpoint(body)
            x, st = _maybe_scan(cfg, body, x, xs)
            caches.append(st)
        else:  # hybrid: groups of `period` ssm layers + shared attn block
            n_groups, tail = hybrid_split(cfg, run.count)
            xs_main, xs_tail = _group_tree(xs, n_groups, period)

            def inner(carry, inp):
                lp, mk = unpack(inp)
                h, st = _ssm_block(cfg, lp, carry, mk, collect_state=collect)
                return h, st

            def group_body(carry, ginp):
                h, st = _maybe_scan(cfg, inner, carry, ginp)
                h, kv = _shared_block(cfg, shared, h, angles,
                                      collect_kv=collect)
                return h, (st, kv)
            if cfg.remat:
                group_body = jax.checkpoint(group_body)
            if n_groups:
                x, (st_main, skv) = _maybe_scan(cfg, group_body, x, xs_main)
            else:
                st_main, skv = None, None
            st_tail = None
            if tail:
                inner_t = jax.checkpoint(inner) if cfg.remat else inner
                x, st_tail = _maybe_scan(cfg, inner_t, x, xs_tail)
            caches.append((st_main, st_tail))
            shared_kv = skv
    return x, {"moe_aux": aux, "moe_z": zl}, caches, shared_kv


# ---------------------------------------------------------------------------
# full-sequence forward (train)
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch,
            masks: Optional[List[Optional[Dict[str, jnp.ndarray]]]] = None):
    x, B, S = embed_inputs(params, cfg, batch)
    angles = _angles_for(cfg, batch, B, S)
    x, aux, _, _ = _run_stack(params, cfg, x, angles, masks, collect=False)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    return logits, {"moe_aux": aux["moe_aux"], "moe_z": aux["moe_z"],
                    "hidden": x}


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------
def softmax_xent(logits, labels, vocab_size=None):
    """Mean xent; labels < 0 are masked out. fp32 math."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def loss_fn(params, cfg: ModelConfig, batch, masks=None):
    logits, aux = forward(params, cfg, batch, masks)
    labels = batch["labels"]
    if cfg.vision_tokens:
        pad = -jnp.ones((labels.shape[0], cfg.vision_tokens), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    loss = softmax_xent(logits, labels)
    total = loss + aux["moe_aux"] + aux["moe_z"]
    metrics = {"xent": loss, "moe_aux": aux["moe_aux"], "moe_z": aux["moe_z"]}
    if cfg.mtp_depth and "mtp" in params:
        h = aux["hidden"]
        emb_next = params["embed"][jnp.maximum(batch["tokens"], 0)]
        if cfg.scale_embeddings:
            emb_next = emb_next * jnp.asarray(
                math.sqrt(cfg.d_model), emb_next.dtype)
        if cfg.vision_tokens:
            h = h[:, cfg.vision_tokens:]
        hcat = jnp.concatenate(
            [h[:, :-1], emb_next[:, 1:]], axis=-1) @ params["mtp"]["proj"]
        B2, S2 = hcat.shape[:2]
        mtp_cfg = (cfg.replace(attention="gqa") if cfg.attention == "mla"
                   else cfg)
        if mtp_cfg.rope_mode == "mrope":
            mtp_cfg = mtp_cfg.replace(rope_mode="standard")
        ang = _angles_for(mtp_cfg, {}, B2, S2)
        hcat = _attn_block(mtp_cfg, params["mtp"]["block"], hcat, ang, None)[0]
        hcat = rmsnorm(hcat, params["mtp"]["ln"], cfg.norm_eps)
        mtp_logits = _lm_logits(params, cfg, hcat)
        lm_labels = batch["labels"]
        mtp_labels = jnp.pad(lm_labels[:, 2:], ((0, 0), (0, 1)),
                             constant_values=-1)[:, :S2]
        mtp_loss = softmax_xent(mtp_logits, mtp_labels)
        total = total + 0.1 * mtp_loss
        metrics["mtp"] = mtp_loss
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def cache_len_for(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(max_len, cfg.sliding_window)
    return max_len


def _stack_zeros(c, n):
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype), c)


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    runs = layer_runs(cfg)
    clen = cache_len_for(cfg, max_len)
    period = cfg.shared_attn_period
    caches = []
    for run in runs:
        if run.kind == "ssm":
            base = ssm_lib.init_ssm_cache(cfg, batch_size, dtype)
            if period:
                n_groups, tail = hybrid_split(cfg, run.count)
                main = jax.tree_util.tree_map(
                    lambda a: jnp.zeros((n_groups, period) + a.shape,
                                        a.dtype), base)
                tl = _stack_zeros(base, tail) if tail else None
                caches.append((main, tl))
            else:
                caches.append(_stack_zeros(base, run.count))
        elif cfg.attention == "mla":
            caches.append(_stack_zeros(
                init_mla_cache(batch_size, max_len, cfg.mla, dtype),
                run.count))
        else:
            caches.append(_stack_zeros(
                init_kv_cache(batch_size, clen, cfg.num_kv_heads,
                              cfg.head_dim, dtype), run.count))
    out = {"runs": caches, "pos": jnp.zeros((batch_size,), jnp.int32)}
    if period:
        ninv = cfg.num_layers // period
        out["shared"] = _stack_zeros(
            init_kv_cache(batch_size, max_len, cfg.num_kv_heads,
                          cfg.head_dim, dtype), max(ninv, 1))
    return out


# ---------------------------------------------------------------------------
# prefill: full sequence -> (last logits, decode-ready cache)
# ---------------------------------------------------------------------------
def _kv_to_cache(cfg, k, v, max_len):
    """k/v (..., S, Hkv, D) -> rolling/padded cache of cache_len_for()."""
    S = k.shape[-3]
    clen = cache_len_for(cfg, max_len)
    if clen == S:
        return k, v
    if clen < S and cfg.sliding_window is None:
        raise ValueError(
            f"prefill max_len={max_len} < prefill length {S} "
            "(remember vision/audio prefix tokens count toward max_len)")
    if clen < S:     # sliding window rolling buffer: slot = pos % clen
        k = jnp.roll(k[..., S - clen:, :, :], S % clen, axis=-3)
        v = jnp.roll(v[..., S - clen:, :, :], S % clen, axis=-3)
        return k, v
    pad = [(0, 0)] * (k.ndim - 3) + [(0, clen - S), (0, 0), (0, 0)]
    return jnp.pad(k, pad), jnp.pad(v, pad)


def prefill(params, cfg: ModelConfig, batch, max_len: Optional[int] = None,
            masks=None):
    """Returns (last_logits (B,V), cache) — or (all_logits, None) for
    encoder-only configs (no decode)."""
    x, B, S = embed_inputs(params, cfg, batch)
    angles = _angles_for(cfg, batch, B, S)
    max_len = max_len or S
    x, _, raw, shared_kv = _run_stack(params, cfg, x, angles, masks,
                                      collect=True)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if not cfg.causal:
        return _lm_logits(params, cfg, x), None
    logits = _lm_logits(params, cfg, x[:, -1])

    runs = layer_runs(cfg)
    caches = []
    for run, rc in zip(runs, raw):
        if run.kind == "ssm":
            if cfg.shared_attn_period:
                caches.append(rc)     # ((groups, period, ...), tail)
            else:
                caches.append(rc)
        elif cfg.attention == "mla":
            ckv, krope = rc           # (count, B, S, rank/ropedim)
            clen = max_len
            if clen > S:
                pad = [(0, 0), (0, 0), (0, clen - S), (0, 0)]
                ckv, krope = jnp.pad(ckv, pad), jnp.pad(krope, pad)
            caches.append(MLACache(ckv, krope))
        else:
            k, v = rc                 # (count, B, S, Hkv, D)
            k, v = _kv_to_cache(cfg, k, v, max_len)
            caches.append(KVCache(k, v))
    cache = {"runs": caches,
             "pos": jnp.full((B,), S, jnp.int32)}
    if cfg.shared_attn_period:
        k, v = shared_kv              # (ninv, B, S, Hkv, D)
        if max_len > S:
            pad = [(0, 0)] * (k.ndim - 3) + [(0, max_len - S), (0, 0),
                                             (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache["shared"] = KVCache(k, v)
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_step(params, cfg: ModelConfig, cache, tokens,
                masks: Optional[List] = None):
    """tokens (B,1) int32 -> (logits (B,V), new cache)."""
    pos = cache["pos"]
    B = tokens.shape[0]
    x = params["embed"][tokens[:, 0]][:, None]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.rope_mode == "mrope":
        p3 = jnp.broadcast_to(pos[None, :, None], (3, B, 1))
        angles = mrope_angles(p3, _rope_dim(cfg), cfg.rope_theta,
                              cfg.mrope_sections)
    elif cfg.rope_mode == "none":
        angles = None
    else:
        angles = rope_angles(pos[:, None], _rope_dim(cfg), cfg.rope_theta)

    runs = layer_runs(cfg)
    masks = masks if masks is not None else [None] * len(runs)
    new_caches = []
    shared = params.get("shared")
    period = cfg.shared_attn_period
    new_shared = cache.get("shared")

    def unpack(inp, n):
        return (inp[:n], inp[n] if len(inp) > n else None)

    for run, rp, rc, rmask in zip(runs, params["runs"], cache["runs"], masks):
        if run.kind == "ssm" and period:
            n_groups, tail = hybrid_split(cfg, run.count)
            rc_main, rc_tail = rc
            xs_p, xs_t = _group_tree(
                (rp, rmask) if rmask is not None else (rp,), n_groups, period)

            def inner(carry, inp):
                (lp, lc), mk = unpack(inp, 2)
                hm = None if mk is None else mk.get("ssm_head_mask")
                hn = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
                o, nc = ssm_lib.ssm_decode(lp["ssm"], cfg, hn,
                                           ssm_lib.SSMCache(*lc),
                                           head_mask=hm)
                return carry + o, nc

            def group_body(carry, ginp):
                h, skv, g = carry
                gp_and_mask, glc = ginp[:-1], ginp[-1]
                inner_xs = (gp_and_mask[0], glc) + (
                    (gp_and_mask[1],) if len(gp_and_mask) > 1 else ())
                # reorder xs for inner: (lp, lc, mk?)
                h, nc = _maybe_scan(cfg, inner, h, inner_xs)
                kv_g = jax.tree_util.tree_map(lambda a: a[g], skv)
                hn = rmsnorm(h, shared["ln1"], cfg.norm_eps)
                a, kv_new = gqa_decode(shared["attn"], cfg, hn, angles,
                                       KVCache(*kv_g), pos)
                h = h + a
                hn = rmsnorm(h, shared["ln2"], cfg.norm_eps)
                h = h + mlp_forward(shared["mlp"], hn, cfg.activation)
                skv = jax.tree_util.tree_map(
                    lambda full, new, idx=g: full.at[idx].set(new),
                    skv, kv_new)
                return (h, skv, g + 1), nc

            if n_groups:
                (x, new_shared, _), nc_main = jax.lax.scan(
                    group_body, (x, new_shared, 0), xs_p + (rc_main,))
            else:
                nc_main = None
            nc_tail = None
            if tail:
                t_xs = (xs_t[0], rc_tail) + ((xs_t[1],) if len(xs_t) > 1
                                             else ())
                x, nc_tail = _maybe_scan(cfg, inner, x, t_xs)
            new_caches.append((nc_main, nc_tail))
        elif run.kind == "ssm":
            def body(carry, inp):
                (lp, lc), mk = unpack(inp, 2)
                hm = None if mk is None else mk.get("ssm_head_mask")
                hn = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
                o, nc = ssm_lib.ssm_decode(lp["ssm"], cfg, hn,
                                           ssm_lib.SSMCache(*lc),
                                           head_mask=hm)
                return carry + o, nc
            xs = (rp, rc) + ((rmask,) if rmask is not None else ())
            x, nc = _maybe_scan(cfg, body, x, xs)
            new_caches.append(nc)
        else:
            is_moe = run.kind == "moe"

            def body(carry, inp):
                (lp, lc), mk = unpack(inp, 2)
                hm = None if mk is None else mk.get("head_mask")
                h = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
                if cfg.attention == "mla":
                    a, nc = mla_decode(lp["attn"], cfg, h, angles,
                                       MLACache(*lc), pos, head_mask=hm)
                else:
                    a, nc = gqa_decode(lp["attn"], cfg, h, angles,
                                       KVCache(*lc), pos, head_mask=hm)
                h = carry + a
                hn = rmsnorm(h, lp["ln2"], cfg.norm_eps)
                if is_moe:
                    em = None if mk is None else mk.get("expert_mask")
                    m, _ = moe_forward(lp["moe"], cfg.moe, hn, cfg.activation,
                                       expert_mask=em)
                else:
                    fm = None if mk is None else mk.get("ffn_mask")
                    m = mlp_forward(lp["mlp"], hn, cfg.activation,
                                    ffn_mask=fm)
                return h + m, nc

            xs = (rp, rc) + ((rmask,) if rmask is not None else ())
            x, nc = _maybe_scan(cfg, body, x, xs)
            new_caches.append(nc)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, x[:, 0])
    new_cache = dict(cache, runs=new_caches, pos=pos + 1)
    if period:
        new_cache["shared"] = new_shared
    return logits, new_cache
