"""Flash attention Pallas kernel (TPU target): online-softmax over KV
blocks, causal / sliding-window masks, GQA (grouped KV heads) native.

Tiling: grid (B, H, nq, nk) with the KV-block dim innermost ("arbitrary" —
the running max / denominator / output accumulator carry across it in VMEM
scratch). Per step the working set is

    q tile (block_q, D) + k/v tiles (block_k, D) + acc (block_q, D)

which for block_q = block_k = 512, D = 128, fp32 accumulation is ~1.5 MB —
comfortably inside a v5e core's 128 MB VMEM, leaving room for the scheduler
to double-buffer the HBM streams. Q/K tile dims are 128-aligned for the MXU.

GQA is handled in the index maps: query head h reads KV head h // group, so
KV tiles are fetched once per group position without a materialized repeat.

Non-contributing KV blocks (fully above the causal diagonal or outside the
sliding window) are skipped with pl.when — for causal prefill that halves
the work, and for sliding-window it makes long-S attention O(S * window).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, seq_k: int):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level skip: causal => KV block entirely in the future;
    # window  => KV block entirely behind every query's window
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        ok = k_pos < seq_k                                # tail padding
        if causal:
            ok &= q_pos >= k_pos
        if window is not None:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(-1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 512,
                           seq_k: Optional[int] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q (B,H,Sq,D), k/v (B,Hkv,Sk,D) -> (B,H,Sq,D). Sq % block_q == 0,
    Sk % block_k == 0 (ops.py pads; ``seq_k`` = the TRUE key length so the
    padded tail is masked out)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    nq, nk = Sq // block_q, Sk // block_k
    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        seq_k=seq_k if seq_k is not None else Sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
