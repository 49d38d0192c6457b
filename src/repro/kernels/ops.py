"""Jit'd high-level wrappers around the Pallas kernels.

``interpret`` defaults to True on the CPU backend, so the same call sites
run in CPU tests (kernel bodies execute through the Pallas interpreter) and
compile to Mosaic on a TPU. Any other backend is an error: interpreting a
kernel on an accelerator would hide compile refusals and time the
interpreter. Shapes are padded to block multiples here, never inside
kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fp8_matmul as _mm
from repro.kernels import mp_attention as _attn
from repro.kernels import quant_cast as _qc
from repro.quant.formats import get_format

__all__ = ["default_interpret", "fp8_linear", "quantize_fp8",
           "flash_attention_mp"]


@functools.cache
def default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels compile for TPU and interpret on "
                           f"CPU; backend {backend!r} is neither")
    return backend == "cpu"


def _pad_to(x: jax.Array, mults: tuple) -> jax.Array:
    pads = []
    for dim, m in zip(x.shape, mults):
        pads.append((0, (-dim) % m))
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def fp8_linear(x: jax.Array, w: jax.Array, *, spec: str = "",
               fmt_name: str = "fp8_e4m3", out_dtype=jnp.bfloat16,
               block: int = 128, interpret=None) -> jax.Array:
    """y = x @ w^T with both operands quantized to fp8 (per-tensor scales).

    x: (M, C); w: (K, C). Fused quantize (amax kernel) + fp8 GEMM kernel.
    """
    interpret = default_interpret() if interpret is None else interpret
    fmt = get_format(fmt_name)
    dt = fmt.dtype or jnp.float8_e4m3fn
    M, C = x.shape
    K = w.shape[0]
    xp = _pad_to(x, (block, block))
    wp = _pad_to(w, (block, block))
    xq, sx_inv = _qc.quantize_fp8(xp, fmt.max_value, dt, interpret=interpret)
    wq, sw_inv = _qc.quantize_fp8(wp, fmt.max_value, dt, interpret=interpret)
    y = _mm.fp8_matmul(xq, wq, sx_inv, sw_inv, block_m=block, block_n=block,
                       block_k=max(block, 128), out_dtype=out_dtype,
                       interpret=interpret)
    return y[:M, :K]


def quantize_fp8(x: jax.Array, fmt_name: str = "fp8_e4m3", interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    fmt = get_format(fmt_name)
    return _qc.quantize_fp8(x, fmt.max_value, fmt.dtype, interpret=interpret)


def flash_attention_mp(q, k, v, *, causal=True, fmt_name=None,
                       quant_probs=None, block=256, interpret=None):
    """(B,H,T,D) attention; fmt_name=None -> bf16, else quantize q/k/v."""
    interpret = default_interpret() if interpret is None else interpret
    sq = sk = sv = 1.0
    if fmt_name is not None:
        fmt = get_format(fmt_name)
        B, H, T, D = q.shape
        qq, sqv = _qc.quantize_fp8(q.reshape(-1, D), fmt.max_value, fmt.dtype,
                                   interpret=interpret)
        kq, skv = _qc.quantize_fp8(k.reshape(-1, D), fmt.max_value, fmt.dtype,
                                   interpret=interpret)
        vq, svv = _qc.quantize_fp8(v.reshape(-1, v.shape[-1]), fmt.max_value,
                                   fmt.dtype, interpret=interpret)
        q = qq.reshape(q.shape)
        k = kq.reshape(k.shape)
        v = vq.reshape(v.shape)
        sq, sk, sv = sqv, skv, svv
        if quant_probs is None:
            quant_probs = True
    return _attn.mp_flash_attention(q, k, v, sq, sk, sv, causal=causal,
                                    block_q=block, block_k=block,
                                    quant_probs=bool(quant_probs),
                                    interpret=interpret)
