"""Mixed-precision flash attention Pallas TPU kernel.

The paper quantizes the two attention BGEMMs (``qk_matmul``, ``av_matmul``).
On TPU these never exist as standalone GEMMs — they live inside a fused
flash-attention kernel — so the TPU-native adaptation is a flash kernel
whose QK^T consumes (optionally) FP8 Q/K with per-tensor scales and whose
PV product consumes FP8 V (probabilities are quantized on the fly in-kernel,
matching eq. (15)'s noise model for the av_matmul lhs).

Grid (B, H, nq, nk), kv innermost; online-softmax running max/denominator
in VMEM scratch; causal blocks that are fully masked are skipped via
``pl.when`` (the block-level advantage the pure-JAX path lacks).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["mp_flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, sv_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
            block_q: int, block_k: int, n_k: int, quant_probs: bool):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sq_ref[0, 0]   # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32) * sk_ref[0, 0]   # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 0)
            ki = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        if quant_probs:  # eq. (15) noise on the av_matmul lhs
            p = p.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32) * sv_ref[0, 0]   # (bk, Dv)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _out():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "quant_probs", "out_dtype", "interpret"))
def mp_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       sq: jax.Array = 1.0, sk: jax.Array = 1.0,
                       sv: jax.Array = 1.0, *, causal: bool = True,
                       block_q: int = 256, block_k: int = 256,
                       quant_probs: bool = False, out_dtype=jnp.bfloat16,
                       interpret: bool = False) -> jax.Array:
    """q,k,v: (B, H, T, D) (any float dtype incl. fp8); scales are the
    dequant multipliers (scale_inv). Returns (B, H, T, Dv) in out_dtype."""
    B, H, T, D = q.shape
    S = k.shape[2]
    Dv = v.shape[3]
    bq, bk = min(block_q, T), min(block_k, S)
    assert T % bq == 0 and S % bk == 0
    grid = (B, H, T // bq, S // bk)
    scalars = [jnp.asarray(s, jnp.float32).reshape(1, 1) for s in (sq, sk, sv)]

    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), causal=causal,
                          block_q=bq, block_k=bk, n_k=grid[3],
                          quant_probs=quant_probs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1), lambda b, h, i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda b, h, i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda b, h, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dv), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, *scalars)
