"""What the benchmark makes from ``--seed``: the float32 weights and channel
masks it hands to the program, the image pool, and each request's image.

Nothing here imports the program. Weights and masks are made on the device
in one jitted call; the pool of base images in another.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs

HERE = os.path.dirname(os.path.abspath(__file__))

#: brightness step between two requests that share a base image: each
#: request's image is distinct, so no answer can be served from a cache
VARIANT_STEP = 2.0 ** -10


def load_config(name: str) -> Dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def seed_key(seed: int, stream: int) -> jnp.ndarray:
    """A raw threefry key for one stream of ``seed`` (any non-negative
    integer, wider than 32 bits included)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _param_shapes(cfg: Dict):
    """(layer index, weight shape, fan-in) of every conv and dense layer,
    at published widths (the masks prune; the program compacts)."""
    c, hw, flat = cfg["input_channels"], cfg["input_hw"], None
    out = []
    for i, spec in enumerate(cfg["layers"]):
        kind = spec["kind"]
        if kind == "conv":
            k = spec["kernel"]
            out.append((i, (k, k, c, spec["out_channels"]), k * k * c))
            hw = (hw + 2 * spec["padding"] - k) // spec["stride"] + 1
            c = spec["out_channels"]
        elif kind == "maxpool":
            hw = (hw - spec["kernel"]) // spec["stride"] + 1
        elif kind == "flatten":
            flat = c * hw * hw
        elif kind == "dense":
            out.append((i, (flat, spec["features"]), flat))
            flat = spec["features"]
    return out


def make_weights(cfg: Dict, seed: int) -> Tuple[Dict, Dict]:
    """-> (params, masks), on the device. He-normal float32 weights and
    small normal biases per layer, ``{"l<i>": {"w", "b"}}`` (conv weights
    HWIO); masks keep, in each pruned conv, the channels of largest L1
    norm, as 0/1 float32 vectors ``{i: (C,)}``."""
    shapes = _param_shapes(cfg)
    widths = costs.deployed_widths(cfg)
    pruned = {i: w for i, w in widths.items()
              if w < cfg["layers"][i]["out_channels"]}

    @jax.jit
    def init(key):
        params, masks = {}, {}
        for (i, shape, fan_in), k in zip(shapes,
                                         jax.random.split(key, len(shapes))):
            kw, kb = jax.random.split(k)
            w = jax.random.normal(kw, shape, jnp.float32) * math.sqrt(
                2.0 / fan_in)
            b = jax.random.normal(kb, shape[-1:], jnp.float32) * 0.05
            params[f"l{i}"] = {"w": w, "b": b}
            if i in pruned:
                l1 = jnp.sum(jnp.abs(w), axis=(0, 1, 2))
                top = jnp.argsort(-l1)[:pruned[i]]
                masks[i] = jnp.zeros(shape[-1], jnp.float32).at[top].set(1.0)
        return params, masks

    return init(seed_key(seed, 0))


def make_pool(cfg: Dict, seed: int, n: int) -> np.ndarray:
    """``n`` base images (n, H, W, C), float32 in [0, 1), made on the
    device and brought to the host once."""
    hw, c = cfg["input_hw"], cfg["input_channels"]
    pool = jax.jit(lambda k: jax.random.uniform(k, (n, hw, hw, c),
                                                jnp.float32),
                   )(seed_key(seed, 1))
    return np.asarray(pool)


def request_image(pool: np.ndarray, order: np.ndarray, i: int) -> np.ndarray:
    """Request ``i``'s image (1, H, W, C): base image ``order[i % P]``,
    brightened by ``(i // P) * VARIANT_STEP``."""
    p = len(order)
    base = pool[order[i % p]][None]
    return base + np.float32((i // p) * VARIANT_STEP)
