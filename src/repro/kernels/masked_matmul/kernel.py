"""Column-masked GEMM Pallas kernel — the compute hot-spot of the paper's
pruning payoff (§3.2): a pruned layer's surviving channels as a masked
matmul, with the mask folded into the epilogue so pruned output channels
never touch HBM as garbage.

Tiling: (block_m, block_n) output tiles, fp32 VMEM accumulator, K streamed
in block_k slices (grid K-dim innermost / "arbitrary" so the accumulator
carries). All block dims should be multiples of the MXU native 128 on real
TPU; interpret=True relaxes this for CPU validation.

On TPU, masked columns still occupy MXU cycles (structured-sparse skip would
need compaction — see repro.core.pruning.masks.compact_* which physically
shrinks weights instead); the kernel's win is the fused epilogue and the
guarantee that downstream layers see exact zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, m_ref, o_ref, acc_ref):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        mask = m_ref[...].astype(jnp.float32)          # (1, block_n)
        o_ref[...] = (acc_ref[...] * mask).astype(o_ref.dtype)


def masked_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray,
                         col_mask: jnp.ndarray,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """a (M, K) @ b (K, N) with output-column mask (N,). Dims must divide
    by their blocks (ops.py pads).

    The mask enters the kernel as a ``(1, N)`` row with ``(1, block_n)``
    blocks: a 1-D operand padded past one block is tiled differently by
    XLA and by Mosaic, which refuses the layout mismatch."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and col_mask.shape == (N,)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    grid = (M // block_m, N // block_n, K // block_k)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b, col_mask.reshape(1, N))
