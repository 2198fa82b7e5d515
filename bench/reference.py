"""The plain reference: a configuration's CNN forward in float32 over the
dense weights and channel masks the benchmark made, in straightforward
``jax.numpy`` at the highest matmul precision. It imports nothing of the
program and takes nothing the program made.

A masked channel is zeroed after its conv and bias, which is what removing
it computes; no weights are quantized, no kernel, batching or codec runs.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def forward(cfg: Dict, params: Dict, masks: Dict, x: jnp.ndarray
            ) -> jnp.ndarray:
    """Logits (B, classes) for images x (B, H, W, C)."""
    for i, spec in enumerate(cfg["layers"]):
        kind = spec["kind"]
        if kind == "conv":
            p, pad = params[f"l{i}"], spec["padding"]
            x = jax.lax.conv_general_dilated(
                x, p["w"], (spec["stride"],) * 2, [(pad, pad)] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=jax.lax.Precision.HIGHEST) + p["b"]
            if i in masks:
                x = x * masks[i]
        elif kind == "relu":
            x = jnp.maximum(x, 0.0)
        elif kind == "maxpool":
            k, s = spec["kernel"], spec["stride"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, s, s, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "dense":
            p = params[f"l{i}"]
            x = jnp.dot(x, p["w"], precision=jax.lax.Precision.HIGHEST) + p["b"]
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return x


def logits(cfg: Dict, params: Dict, masks: Dict, images: np.ndarray,
           block: int = 16) -> np.ndarray:
    """Reference logits of ``images`` (N, H, W, C), ``block`` rows at a
    time (the last block padded), so that it fits beside nothing else."""
    fn = jax.jit(lambda p, m, x: forward(cfg, p, m, x))
    n = images.shape[0]
    out = []
    for s in range(0, n, block):
        x = images[s:s + block]
        pad = block - x.shape[0]
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        out.append(np.asarray(fn(params, masks, x))[:block - pad])
    return np.concatenate(out)
