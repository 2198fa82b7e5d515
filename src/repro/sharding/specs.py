"""Sharding planner: PartitionSpec pytrees for params, batches, and
caches, per (config, mesh).

Strategy (baseline; §Perf iterates on it):
  * 2-D weight sharding — every large matmul weight shards its d_model-side
    dim over the combined data axes (FSDP-style; gathered per layer inside
    the scan) and its output/expert dim over "model" (Megatron-style).
    This is what lets 340B/671B configs fit 16 GB/chip (DESIGN.md §5).
  * MoE expert dim shards over "model" (expert parallelism).
  * Batch shards over ("pod","data") / ("data",) when divisible; otherwise
    the sequence (context parallelism) or nothing (B=1 long-context decode).
  * Norms/scalars replicate.

Rules are name-based over the param tree paths, so they apply uniformly to
stacked (scan) and unstacked (shared/mtp) blocks.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def path_key(p) -> str:
    """Robust tree-path element -> string (DictKey/SequenceKey/GetAttrKey)."""
    for attr in ("key", "idx", "name"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def path_keys(path):
    return tuple(path_key(p) for p in path)


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], str]:
    """(data_axes, model_axis) from a production mesh."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data"), "model"
    return ("data",), "model"


def _divisible(n: int, mesh: Mesh, axes) -> bool:
    size = int(np.prod([mesh.shape[a] for a in (axes if isinstance(axes, tuple)
                                                else (axes,))]))
    return n % size == 0


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
_COL_NAMES = {"wq", "wk", "wv", "w_up", "w_gate", "w_dq", "w_uq", "w_dkv",
              "w_uk", "w_uv", "w_in", "w_up_sh", "w_gate_sh", "proj"}
_ROW_NAMES = {"wo", "w_down", "w_out", "w_down_sh"}
_BIAS_NAMES = {"bq", "bk", "bv"}
_REPL_NAMES = {"ln1", "ln2", "ln", "final_norm", "q_norm", "kv_norm",
               "norm_scale", "A_log", "dt_bias", "D", "conv_b", "w_router"}


def _leaf_spec(path_keys, leaf, cfg: ModelConfig, data, model,
               shard_data_dim: bool) -> P:
    name = path_keys[-1]
    in_moe = "moe" in path_keys
    nd = np.ndim(leaf)
    dspec = data if shard_data_dim else None

    def lead(base):
        return P(*([None] * (nd - len(base)) + list(base)))

    if name == "embed":
        return P("model", dspec)
    if name == "lm_head":
        return P(dspec, "model")
    if name in _REPL_NAMES:
        return lead([None] * min(nd, 1))
    if name == "conv_w":
        return lead([None, "model"])
    if name in _BIAS_NAMES:
        return lead(["model"])
    if in_moe and name in ("w_up", "w_gate"):
        return lead(["model", dspec, None])
    if in_moe and name == "w_down":
        return lead(["model", None, dspec])
    if name in _COL_NAMES:
        return lead([dspec, "model"])
    if name in _ROW_NAMES:
        return lead(["model", dspec])
    # default: replicate
    return P()


def param_specs(params, cfg: ModelConfig, mesh: Mesh,
                shard_data_dim: bool = True):
    """PartitionSpec pytree matching ``params``."""
    data, model = mesh_axes(mesh)

    def spec_for(path, leaf):
        keys = path_keys(path)
        sp = _leaf_spec(keys, leaf, cfg, data, model, shard_data_dim)
        # drop axes that do not divide evenly (GSPMD handles uneven, but we
        # prefer clean layouts; uneven dims fall back to replication on
        # that axis)
        dims = np.shape(leaf)
        fixed = []
        for dim, ax in zip(dims, tuple(sp) + (None,) * (len(dims) - len(sp))):
            if ax is None:
                fixed.append(None)
            elif _divisible(dim, mesh, ax):
                fixed.append(ax)
            else:
                fixed.append(None)
        return P(*fixed)

    return jax.tree_util.tree_map_with_path(spec_for, params)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------
def batch_specs(batch, cfg: ModelConfig, mesh: Mesh):
    data, _ = mesh_axes(mesh)

    def spec_for(path, leaf):
        keys = path_keys(path)
        name = keys[-1]
        shape = np.shape(leaf)
        if name == "mrope_positions":           # (3, B, S)
            b_ok = _divisible(shape[1], mesh, data)
            return P(None, data if b_ok else None, None)
        if not shape:
            return P()
        b_ok = _divisible(shape[0], mesh, data)
        if b_ok:
            return P(*([data] + [None] * (len(shape) - 1)))
        # small batch: shard sequence instead when possible
        if len(shape) >= 2 and _divisible(shape[1], mesh, data):
            return P(*([None, data] + [None] * (len(shape) - 2)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec_for, batch)


def cache_specs(cache, cfg: ModelConfig, mesh: Mesh):
    """KV/MLA/SSM cache sharding.

    KVCache   (L, B, S, Hkv, D): B over data if divisible, else S over data
              (context parallelism); Hkv over model if divisible else D.
    MLACache  (L, B, S, rank): rank over model.
    SSMCache  conv (L, B, K-1, cdim): cdim over model.
              state (L, B, H, P, N): H over model.
    pos       replicated.
    """
    data, model = mesh_axes(mesh)

    def spec_for(path, leaf):
        keys = path_keys(path)
        name = keys[-1]
        shape = np.shape(leaf)
        if name == "pos" or not shape:
            return P()
        if name == "conv":
            return P(*([None] * (len(shape) - 1) + [
                model if _divisible(shape[-1], mesh, model) else None]))
        if name == "state":
            h_ax = model if _divisible(shape[-3], mesh, model) else None
            out = [None] * len(shape)
            out[-3] = h_ax
            b_idx = len(shape) - 4
            if b_idx >= 0 and _divisible(shape[b_idx], mesh, data):
                out[b_idx] = data
            return P(*out)
        if name in ("k", "v"):                  # (..., B, S, Hkv, D)
            out = [None] * len(shape)
            b_idx, s_idx, h_idx, d_idx = (len(shape) - 4, len(shape) - 3,
                                          len(shape) - 2, len(shape) - 1)
            if _divisible(shape[b_idx], mesh, data):
                out[b_idx] = data
            elif _divisible(shape[s_idx], mesh, data):
                out[s_idx] = data
            if _divisible(shape[h_idx], mesh, model):
                out[h_idx] = model
            elif _divisible(shape[d_idx], mesh, model):
                out[d_idx] = model
            return P(*out)
        if name in ("ckv", "krope"):            # (L, B, S, rank)
            out = [None] * len(shape)
            if _divisible(shape[1], mesh, data):
                out[1] = data
            elif _divisible(shape[2], mesh, data):
                out[2] = data
            if _divisible(shape[-1], mesh, model):
                out[-1] = model
            return P(*out)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def to_shardings(specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))
