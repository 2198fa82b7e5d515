"""The jittable transformer train step."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tr
from repro.optim import Optimizer


def make_train_step(cfg: ModelConfig, optimizer: Optimizer, masks=None,
                    grad_accum: int = 1):
    """One optimizer step. ``grad_accum > 1`` scans over microbatches and
    accumulates fp32 grads — divides live activation memory by the factor
    at the cost of one scan (EXPERIMENTS.md §Perf-2 it3: the lever that
    fits qwen2-7b train_4k into 16 GB/chip)."""
    if grad_accum == 1:
        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                tr.loss_fn, has_aux=True)(params, cfg, batch, masks)
            params, opt_state = optimizer.update(grads, opt_state, params)
            return params, opt_state, metrics
        return train_step

    def train_step(params, opt_state, batch):
        def split_mb(path, a):
            from repro.sharding.specs import path_keys
            # mrope_positions is (3, B, S): batch is dim 1
            bdim = 1 if path_keys(path)[-1] == "mrope_positions" else 0
            assert a.shape[bdim] % grad_accum == 0, (path, a.shape)
            if bdim == 0:
                return a.reshape((grad_accum, a.shape[0] // grad_accum)
                                 + a.shape[1:])
            out = a.reshape(a.shape[:1] + (grad_accum,
                                           a.shape[1] // grad_accum)
                            + a.shape[2:])
            return jnp.moveaxis(out, 1, 0)

        mb = jax.tree_util.tree_map_with_path(split_mb, batch)

        def body(gsum, mbatch):
            (_, metrics), g = jax.value_and_grad(
                tr.loss_fn, has_aux=True)(params, cfg, mbatch, masks)
            gsum = jax.tree_util.tree_map(
                lambda acc, gg: acc + gg.astype(jnp.float32), gsum, g)
            return gsum, metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        gsum, ms = jax.lax.scan(body, zeros, mb)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / grad_accum).astype(p.dtype), gsum, params)
        params, opt_state = optimizer.update(grads, opt_state, params)
        metrics = jax.tree_util.tree_map(lambda a: a.mean(), ms)
        return params, opt_state, metrics

    return train_step

