"""Scaled FP8 GEMM Pallas TPU kernel.

The execution primitive of the paper's MP configurations: a linear layer
whose operands are stored/consumed in FP8 with per-tensor scales and fp32
MXU accumulation, dequantized in the epilogue::

    Y = (Xq * sx_inv) @ (Wq * sw_inv)^T
      = (Xq @ Wq^T) * (sx_inv * sw_inv)      # scales fold into the epilogue

Tiling: (bm x bk) x (bn x bk) -> (bm x bn) blocks, K innermost ("arbitrary")
so partial products accumulate in a VMEM fp32 scratch; M/N grid dims are
parallel. Block shapes must be MXU-aligned (multiples of 128 on the matmul
dims; 32 on the fp8 lane dim is allowed but 128 keeps layouts trivial).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["fp8_matmul"]


def _kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bk)
    w = w_ref[...].astype(jnp.float32)          # (bn, bk)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        scale = sx_ref[0, 0] * sw_ref[0, 0]
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def fp8_matmul(xq: jax.Array, wq: jax.Array, sx_inv: jax.Array,
               sw_inv: jax.Array, *, block_m: int = 256, block_n: int = 256,
               block_k: int = 512, out_dtype=jnp.bfloat16,
               interpret: bool = False) -> jax.Array:
    """xq: (M, K) fp8; wq: (N, K) fp8; scales: scalars. Returns (M, N)."""
    M, K = xq.shape
    N, K2 = wq.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"pad shapes to block multiples: {(M, N, K)} vs {(bm, bn, bk)}")
    grid = (M // bm, N // bn, K // bk)

    sx = jnp.asarray(sx_inv, jnp.float32).reshape(1, 1)
    sw = jnp.asarray(sw_inv, jnp.float32).reshape(1, 1)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, wq, sx, sw)
