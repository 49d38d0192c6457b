"""Fused paged-attention decode Pallas TPU kernel.

Single-query (decode-step) attention computed *directly against the paged
KV store*: block-table indirection is resolved inside the kernel grid via
scalar prefetch — each grid step's BlockSpec index map reads the row's block
table and DMAs exactly one physical KV block — so per-step HBM traffic
scales with each row's *live* tokens instead of the provisioned
``max_blocks * block_size`` capacity that ``nn.layers.paged_gather``
materializes per layer. fp8 KV caches are dequantized in-register (never
written wide to HBM), which is what preserves the fp8-cache bandwidth win
at the decode step.

Layout contract (mirrors ``nn/layers.py`` paged caches):

* ``q``: (B, Hkv, G, Dk) — one query token per row, GQA via head-group
  reshape (H = Hkv * G). MLA absorbed decode passes Hkv=1, G=H.
* ``k``/``v``: (n_blocks, Hkv, block_size, D) head-major physical storage,
  so each grid step's block ``(1, 1, block_size, D)`` is one head's page
  and its last two dims tile on the TPU. ``v=None`` reuses ``k`` as values
  (MLA: both scores and context contract the latent ``ckv``). ``q2``/``k2``
  optionally add a second score operand (MLA RoPE part):
  ``s = q @ k^T + q2 @ k2^T``.
* ``block_tables``: (B, max_blocks) int32, -1 = unallocated. Dead pages are
  clamped to the trash block 0 *in the index map*, so consecutive dead pages
  revisit the same block and the pipeline elides their copies — a row costs
  ~(live pages + 1) block fetches, not ``max_blocks``.
* ``lengths``: (B,) int32 live-token count (query position + 1). Keys at
  logical positions >= ``lengths[b]`` — stale or trash block contents — are
  masked before the softmax; with ``window`` set, positions at or below
  ``lengths[b] - 1 - window`` are masked too, and pages entirely outside
  the window are skipped like dead pages.

Numerics: two grid phases per row — phase 0 computes masked scores into a
VMEM scratch (tracking the running row max), phase 1 normalizes against the
*final* max/denominator and accumulates probs @ V. Unlike one-pass
flash-style rescaling, the probabilities here are bit-identical to the
materialized-softmax reference (``_reference_attention`` /
``_mla_decode_absorbed``) before the optional ``probs_dtype`` cast, so
greedy decode tokens match the ``paged_gather`` path. ``score_dtype`` /
``probs_dtype`` reproduce the reference's intermediate casts (bf16 for GQA
attention; None = keep f32, the MLA absorbed path). Each operand fetches a
live block only in the phase that consumes it (K in phase 0, V in phase 1
— both once per live block); the MLA ``v=None`` path reads its ``ckv``
blocks in both phases because keys and values share that storage.

Score scratch: token-major ``(max_blocks * block_size, G)`` f32. A page's
scores are rows ``[j*bs, (j+1)*bs)`` — a sublane offset, aligned whenever
``block_size`` is a multiple of 8, which the TPU compiler needs (a lane
offset of ``j*bs`` is not provably 128-aligned and is refused). The
denominator reduces the scratch over its leading axis, which on the CPU
backend sums in the reference's order. Each token pads ``G`` (<= 128) to a
128-lane row, so the scratch costs 512 B per token of ``max_len``: on a TPU
v5e under the default 16 MiB scoped-VMEM limit the kernel compiles at
``max_len`` 30720 and is refused at 32768 (B=8, Hkv=8, G=4, D=64,
block 16). Longer contexts need the single-pass softmax of ROADMAP A3.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["paged_decode_attention", "BIG_WINDOW"]

# matches the reference path's mask fill (jnp.finfo(f32).min, not -inf: a
# fully-masked row then softmaxes to uniform garbage instead of NaN)
NEG = float(jnp.finfo(jnp.float32).min)
BIG_WINDOW = 1 << 30              # "no window" sentinel (fits int32)


def _kernel(bt_ref, len_ref, win_ref, q_ref, *rest, bs: int, n_pages: int,
            scale: float, scale_mode: str, score_dtype, probs_dtype,
            k_scale: float, v_scale: float, has_k2: bool, v_from_k: bool):
    refs = list(rest)
    k_ref = refs.pop(0)
    q2_ref = k2_ref = None
    if has_k2:
        q2_ref = refs.pop(0)
        k2_ref = refs.pop(0)
    v_ref = k_ref if v_from_k else refs.pop(0)
    o_ref, m_ref, l_ref, s_ref, acc_ref = refs

    b = pl.program_id(0)
    ph, j = pl.program_id(2), pl.program_id(3)
    ln = len_ref[b]
    win = win_ref[0]
    start = j * bs
    rows = pl.ds(pl.multiple_of(start, bs), bs)   # this page's score rows
    # any key of this page both causally live and inside the window?
    page_live = (start < ln) & (start + bs > ln - win)

    @pl.when((ph == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.full_like(s_ref, NEG)  # dead pages stay masked

    @pl.when((ph == 0) & page_live)
    def _scores():
        q = q_ref[0, 0]                                   # (G, Dk)
        k = _dequant(k_ref[0, 0], q.dtype, k_scale)       # (bs, Dk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_k2:
            q2 = q2_ref[0, 0]
            k2 = _dequant(k2_ref[0, 0], q2.dtype, k_scale)
            s = s + jax.lax.dot_general(q2, k2, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        if score_dtype is not None:   # reference rounds scores (bf16 GQA)
            s = s.astype(score_dtype)
        s = s.astype(jnp.float32).T                       # (bs, G)
        s = s / scale if scale_mode == "div" else s * scale
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        live = (kpos < ln) & (kpos > ln - 1 - win)
        s = jnp.where(live, s, NEG)
        s_ref[rows, :] = s
        m_ref[...] = jnp.maximum(m_ref[...], jnp.max(s, 0, keepdims=True))

    @pl.when((ph == 1) & (j == 0))
    def _denominator():
        l_ref[...] = jnp.sum(jnp.exp(s_ref[...] - m_ref[...]), 0,
                             keepdims=True)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((ph == 1) & page_live)
    def _context():
        p = (jnp.exp(s_ref[rows, :] - m_ref[...]) / l_ref[...]).T  # (G, bs)
        if probs_dtype is not None:   # reference rounds probs (bf16 GQA)
            p = p.astype(probs_dtype)
        v = _dequant(v_ref[0, 0], p.dtype, v_scale)       # (bs, Dv)
        acc_ref[...] += jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((ph == 1) & (j == n_pages - 1))
    def _out():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def _dequant(x: jax.Array, dtype, scale: float) -> jax.Array:
    """In-register dequant of a (possibly fp8) KV block. ``scale`` is the
    per-tensor dequant multiplier (scale_inv); 1.0 skips the multiply so the
    unscaled path stays bit-identical to ``paged_gather``'s plain upcast."""
    if scale == 1.0:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * scale).astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "scale_mode", "score_dtype",
                              "probs_dtype", "k_scale", "v_scale",
                              "out_dtype", "interpret"))
def _call(q, k, v, q2, k2, block_tables, lengths, window, *, scale,
          scale_mode, score_dtype, probs_dtype, k_scale, v_scale,
          out_dtype, interpret):
    B, Hkv, G, Dk = q.shape
    bs = k.shape[2]
    n_pages = block_tables.shape[1]
    v_from_k = v is None
    Dv = k.shape[-1] if v_from_k else v.shape[-1]
    has_k2 = k2 is not None

    def kv_map(keep0, keep1):
        # dead pages map to the trash block 0 (consecutive revisits elide
        # their DMA); phases that don't consume the operand also map to 0
        def index(b, h, ph, j, bt, ln, wn):
            live = ((j * bs < ln[b]) & (j * bs + bs > ln[b] - wn[0])
                    & ((ph == 0) & keep0 | (ph == 1) & keep1))
            return (jnp.where(live, jnp.maximum(bt[b, j], 0), 0), h, 0, 0)
        return index

    def q_map(b, h, ph, j, *_):
        return (b, h, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, G, Dk), q_map),
                pl.BlockSpec((1, 1, bs, Dk), kv_map(True, v_from_k))]
    operands = [q, k]
    if has_k2:
        in_specs += [pl.BlockSpec((1, 1, G, q2.shape[-1]), q_map),
                     pl.BlockSpec((1, 1, bs, k2.shape[-1]),
                                  kv_map(True, False))]
        operands += [q2, k2]
    if not v_from_k:
        in_specs.append(pl.BlockSpec((1, 1, bs, Dv), kv_map(False, True)))
        operands.append(v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, 2, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, G), jnp.float32),            # running max
            pltpu.VMEM((1, G), jnp.float32),            # denominator
            pltpu.VMEM((n_pages * bs, G), jnp.float32),  # masked scores
            pltpu.VMEM((G, Dv), jnp.float32),            # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_pages=n_pages, scale=scale,
                          scale_mode=scale_mode, score_dtype=score_dtype,
                          probs_dtype=probs_dtype, k_scale=k_scale,
                          v_scale=v_scale, has_k2=has_k2, v_from_k=v_from_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, window, *operands)


def paged_decode_attention(q: jax.Array, k: jax.Array, v: Optional[jax.Array],
                           block_tables: jax.Array, lengths: jax.Array, *,
                           window=None, q2: Optional[jax.Array] = None,
                           k2: Optional[jax.Array] = None,
                           scale: float, scale_mode: str = "div",
                           score_dtype=None, probs_dtype=None,
                           k_scale: float = 1.0, v_scale: float = 1.0,
                           out_dtype=None, interpret: Optional[bool] = None
                           ) -> jax.Array:
    """Single-query paged attention: (B, Hkv, G, Dv) in ``out_dtype``.

    ``window`` may be None (full causal), a python int, or a traced int32
    scalar (scan-mode per-layer windows); ``scale_mode`` selects
    ``s / scale`` (GQA reference) vs ``s * scale`` (MLA absorbed reference).
    Rows whose ``lengths`` entry is 0 produce zeros. ``interpret`` defaults
    to True on the CPU backend so the same call site runs in CPU CI and
    compiles to Mosaic on a TPU. On TPU, ``block_size`` must be a multiple
    of 8; fp8 caches want ``block_size`` >= the fp8 min sublane tile (32);
    smaller blocks still compile via Mosaic padding but waste tile
    bandwidth.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    if window is None:
        window = BIG_WINDOW
    window = jnp.asarray(window, jnp.int32).reshape(1)
    lengths = jnp.asarray(lengths, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    out_dtype = q.dtype if out_dtype is None else out_dtype
    return _call(q, k, v, q2, k2, block_tables, lengths, window,
                 scale=float(scale), scale_mode=scale_mode,
                 score_dtype=score_dtype, probs_dtype=probs_dtype,
                 k_scale=float(k_scale), v_scale=float(v_scale),
                 out_dtype=out_dtype, interpret=interpret)
