"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["fp8_matmul_ref", "amax_ref", "scale_cast_ref",
           "mp_flash_attention_ref", "paged_decode_attention_ref"]


def fp8_matmul_ref(xq: jax.Array, wq: jax.Array, sx_inv, sw_inv,
                   out_dtype=jnp.bfloat16) -> jax.Array:
    y = jnp.einsum("mk,nk->mn", xq.astype(jnp.float32), wq.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return (y * sx_inv * sw_inv).astype(out_dtype)


def amax_ref(x: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def scale_cast_ref(x: jax.Array, scale, dtype=jnp.float8_e4m3fn) -> jax.Array:
    return (x.astype(jnp.float32) * scale).astype(dtype)


def _paged_deq(cache, block_tables, dtype, scale):
    """Gather-to-logical-order dequant (the ``paged_gather`` semantics)."""
    n_blocks, H, bs, D = cache.shape           # head-major pages
    B, npg = block_tables.shape
    g = jnp.take(cache, jnp.maximum(block_tables, 0), axis=0)
    g = jnp.transpose(g, (0, 1, 3, 2, 4)).reshape(B, npg * bs, H, D)
    if scale != 1.0:
        return (g.astype(jnp.float32) * scale).astype(dtype)
    return g.astype(dtype)


def paged_decode_attention_ref(q, k, v, block_tables, lengths, *,
                               window=None, q2=None, k2=None, scale,
                               scale_mode="div", score_dtype=None,
                               probs_dtype=None, k_scale=1.0, v_scale=1.0,
                               out_dtype=None):
    """Gather-then-attend oracle with the exact reference-path numerics
    (``nn.layers._reference_attention`` / ``_mla_decode_absorbed``): gather
    each row's blocks into logical order, mask by length/window, softmax in
    f32 with the reference's intermediate casts. Shapes as in
    :func:`repro.kernels.paged_attention.paged_decode_attention`."""
    B, Hkv, G, Dk = q.shape
    out_dtype = q.dtype if out_dtype is None else out_dtype
    kg = _paged_deq(k, block_tables, q.dtype, k_scale)      # (B, S, Hkv, Dk)
    s = jnp.einsum("BKGD,BSKD->BKGS", q, kg,
                   preferred_element_type=jnp.float32)
    if q2 is not None:
        k2g = _paged_deq(k2, block_tables, q2.dtype, k_scale)
        s = s + jnp.einsum("BKGD,BSKD->BKGS", q2, k2g,
                           preferred_element_type=jnp.float32)
    if score_dtype is not None:
        s = s.astype(score_dtype)
    s = s.astype(jnp.float32)
    s = s / scale if scale_mode == "div" else s * scale
    S = kg.shape[1]
    kpos = jnp.arange(S, dtype=jnp.int32)[None, :]
    live = kpos < lengths[:, None]
    if window is not None:
        live &= kpos > (lengths[:, None] - 1 - window)
    s = jnp.where(live[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    if probs_dtype is not None:
        p = p.astype(probs_dtype)
    vsrc = k if v is None else v
    vg = _paged_deq(vsrc, block_tables, p.dtype, v_scale)
    o = jnp.einsum("BKGS,BSKD->BKGD", p, vg,
                   preferred_element_type=jnp.float32)
    # rows with length 0 attend nothing in the kernel; zero them here too
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
    return o.astype(out_dtype)


def mp_flash_attention_ref(q, k, v, sq=1.0, sk=1.0, sv=1.0, *,
                           causal=True, quant_probs=False,
                           out_dtype=jnp.bfloat16):
    """Materialized-softmax oracle with identical quantization semantics."""
    B, H, T, D = q.shape
    S = k.shape[2]
    qf = q.astype(jnp.float32) * sq
    kf = k.astype(jnp.float32) * sk
    vf = v.astype(jnp.float32) * sv
    s = jnp.einsum("bhtd,bhsd->bhts", qf, kf) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if quant_probs:
        p = p.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    o = jnp.einsum("bhts,bhsd->bhtd", p, vf) / jnp.maximum(l, 1e-30)
    return o.astype(out_dtype)
