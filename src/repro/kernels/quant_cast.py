"""Fused amax -> scale -> FP8-cast Pallas kernel pair.

Runtime activation quantization is the per-op overhead the MP configuration
pays on every quantized layer (the RooflineGainModel charges read(bf16) +
write(fp8) for it). Fusing the reduction and the cast keeps it at exactly
one read + one tiny write + one read + one fp8 write.

Two kernels because amax is a full reduction: (1) per-row-tile amax partials,
(2) scale+cast with the folded scalar. Both tile (bm x N) row blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["amax", "scale_cast", "quantize_fp8"]


def _amax_kernel(x_ref, o_ref):
    # one whole (8, 128) f32 tile per row block: a (1, 1) output block is
    # refused by the TPU compiler (last two block dims must tile)
    o_ref[...] = jnp.full(o_ref.shape, jnp.max(
        jnp.abs(x_ref[...].astype(jnp.float32))), jnp.float32)


def _cast_kernel(x_ref, s_ref, o_ref):
    o_ref[...] = (x_ref[...].astype(jnp.float32) * s_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def amax(x: jax.Array, *, block_m: int = 256, interpret: bool = False):
    """Per-tensor abs-max of a 2D array via tiled partial reduction."""
    M, N = x.shape
    bm = min(block_m, M)
    assert M % bm == 0
    grid = (M // bm,)
    partial = pl.pallas_call(
        _amax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 8, 128), jnp.float32),
        interpret=interpret,
    )(x)
    return jnp.max(partial)


@functools.partial(jax.jit, static_argnames=("dtype", "block_m", "interpret"))
def scale_cast(x: jax.Array, scale: jax.Array, *, dtype=jnp.float8_e4m3fn,
               block_m: int = 256, interpret: bool = False) -> jax.Array:
    M, N = x.shape
    bm = min(block_m, M)
    assert M % bm == 0
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _cast_kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), dtype),
        interpret=interpret,
    )(x, s)


def quantize_fp8(x: jax.Array, max_value: float = 448.0,
                 dtype=jnp.float8_e4m3fn, interpret: bool = False):
    """Returns (xq, scale_inv): the fused amax->scale->cast pipeline."""
    a = amax(x, interpret=interpret)
    scale = max_value / jnp.maximum(a, 1e-12)
    xq = scale_cast(x, scale, dtype=dtype, interpret=interpret)
    return xq, 1.0 / scale
