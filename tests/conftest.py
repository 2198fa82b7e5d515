"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches see
the host's single CPU device."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCH_IDS, get_smoke_config


def smoke_batch(cfg, B=2, S=16, seed=0, with_labels=True):
    """Batch matching a (possibly multimodal) smoke config."""
    key = jax.random.PRNGKey(seed)
    batch = {}
    if cfg.embeds_input:
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.dtype(cfg.dtype))
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        if cfg.vision_tokens:
            batch["vision_embeds"] = jax.random.normal(
                key, (B, cfg.vision_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
    if with_labels:
        batch["labels"] = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                             (B, S), 0, cfg.vocab_size)
    return batch


def moe_no_drop(cfg):
    """Raise MoE capacity so routing never drops (for exact-consistency
    tests; dropping is data-dependent and differs between T=B*S and T=B)."""
    if cfg.moe is None:
        return cfg
    import dataclasses
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k))


@pytest.fixture(scope="session")
def arch_ids():
    return ARCH_IDS


@pytest.fixture(scope="session", params=ARCH_IDS)
def smoke_cfg(request):
    return get_smoke_config(request.param).replace(dtype="float32")
