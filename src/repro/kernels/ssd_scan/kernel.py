"""Chunked SSD (Mamba2 state-space duality) Pallas kernel.

The SSD layer is the dominant op of the attention-free architectures
(mamba2-2.7b, zamba2-1.2b). Its TPU-native form is exactly the chunked
algorithm: per chunk a handful of (Q x Q) / (Q x N) / (Q x P) matmuls that
hit the MXU, plus an O(S/Q) sequential state pass.

Tiling: grid (B, H, n_chunks), chunk dim innermost/"arbitrary" — the
(P, N) state carries across chunks in fp32 VMEM scratch (the recurrence
s_c = decay * s_{c-1} + B^T (dt . decay_to_end . x) is associative in c but
cheap enough that a serial carry wastes nothing at Q = 256).

Per-step working set for Q=256, P=64, N=128:
    x (Q,P) + B,C (Q,N) + L (Q,Q) + state (P,N) fp32  ~ 0.5 MB << VMEM.

GQA-style group sharing (G groups of heads share B/C) is handled in the
index map: head h reads group h // (H/G).

Head masking (the DDPG pruner's axis, paper §3.2) multiplies y per head —
folded into the epilogue here so a pruned head never writes to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, hm_ref,
                y_ref, fs_ref, state_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # (Q,)
    a = a_ref[0].astype(jnp.float32)                  # scalar A_h (negative)
    b = b_ref[0, :, 0].astype(jnp.float32)            # (Q, N)
    c = c_ref[0, :, 0].astype(jnp.float32)            # (Q, N)

    dA = dt * a                                       # (Q,)
    cs = jnp.cumsum(dA)                               # (Q,)
    # intra-chunk decay matrix L[i,j] = exp(cs_i - cs_j) for i >= j
    d = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(d), 0.0)          # (Q, Q)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * L * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # carry-in contribution: C_q . state^T, decayed to step q
    state = state_ref[...]                            # (P, N)
    y += jnp.exp(cs)[:, None] * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # chunk-final state update
    decay_to_end = jnp.exp(cs[-1] - cs)               # (Q,)
    xw = x * (dt * decay_to_end)[:, None]             # (Q, P)
    new_contrib = jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (P, N)
    state_ref[...] = state * jnp.exp(cs[-1]) + new_contrib

    y = y * hm_ref[0].astype(jnp.float32)             # pruning epilogue
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _done():
        fs_ref[0, 0] = state_ref[...].astype(fs_ref.dtype)


def ssd_scan_pallas(xh: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                    Bm: jnp.ndarray, Cm: jnp.ndarray,
                    head_mask: jnp.ndarray,
                    chunk: int = 256, interpret: bool = False):
    """xh (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,G,N); head_mask (H,).
    S % chunk == 0 (ops.py pads). Returns (y (B,S,H,P), state (B,H,P,N))."""
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0 and H % G == 0
    rep = H // G
    nc = S // chunk
    grid = (B, H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, fs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda b, h, c, r=rep: (b, c, h // r, 0)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda b, h, c, r=rep: (b, c, h // r, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), xh.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dt, A, Bm, Cm, head_mask)
    return y, fs
