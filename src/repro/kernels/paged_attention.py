"""Fused paged-attention decode Pallas TPU kernel.

Single-query (decode-step) attention computed *directly against the paged
KV store*: the block table is scalar-prefetched and the kernel copies each
row's live pages from HBM itself, so per-step HBM traffic scales with each
row's *live* tokens instead of the provisioned ``max_blocks * block_size``
capacity that ``nn.layers.paged_gather`` materializes per layer. fp8 KV
caches are dequantized in-register (never written wide to HBM), which is
what preserves the fp8-cache bandwidth win at the decode step.

Layout contract (mirrors ``nn/layers.py`` paged caches):

* ``q``: (B, Hkv, G, Dk) — one query token per row, GQA via head-group
  reshape (H = Hkv * G). MLA absorbed decode passes Hkv=1, G=H.
* ``k``/``v``: (n_blocks, Hkv, block_size, D) head-major physical storage,
  so one page ``k[blk]`` holds every head's ``block_size`` tokens.
  ``v=None`` reuses ``k`` as values (MLA: both scores and context contract
  the latent ``ckv``). ``q2``/``k2`` optionally add a second score operand
  (MLA RoPE part): ``s = q @ k^T + q2 @ k2^T``.
* ``block_tables``: (B, max_blocks) int32, -1 = unallocated (a live page
  whose entry is -1, a vacant row's, reads the trash block 0).
* ``lengths``: (B,) int32 live-token count (query position + 1). Keys at
  logical positions >= ``lengths[b]`` — stale or trash block contents — are
  masked before the softmax; with ``window`` set, positions at or below
  ``lengths[b] - 1 - window`` are masked too.

Grid and copies: a grid cell is one row and a block of ``hb`` KV heads.
All heads share a cell (``hb = Hkv``) while ``Hkv * G <= 128``, so the
score scratch pads to the lanes it would pad to for one head; otherwise
each head gets a cell (``hb = 1``). The kernel works in *groups* of ``P``
consecutive logical pages, ``P = pages_per_step(block_size, n_pages)``:
128 tokens per head (8 pages of 16), capped at the table width. A page is
copied whole — every head of the cell at once, ``(hb, block_size, D)`` —
and only where it holds a key both causally live and inside the window;
no block that only stale table entries point at is ever fetched. Pages
reach VMEM one of two ways, by shape (``copies_in_loop``):

* 128-lane pages (the served GQA models): grid ``(B, Hkv // hb)``. K/V stay
  in HBM and each phase loops over the row's live groups, starting one
  ``make_async_copy`` per live page of the next group into one half of a
  double buffer while the other half computes; the last phase-0 step
  starts phase 1's first group. A row costs two steps per live group.
* narrower pages (64-lane heads, MLA's RoPE keys), whose HBM rows the TPU
  compiler pads and whose slices it refuses to copy: grid ``(B, Hkv // hb,
  2, n_groups)`` with ``P`` BlockSpecs per page operand, page ``j``'s index
  map reading the block table at ``g*P + j``; dead pages, and phases that
  do not consume the operand, map to the trash block 0, whose revisits
  elide their copies, and a group with no live page runs no compute.

``kernel_steps`` counts a call's steps and copies from the same rules.

Numerics: two phases per row — phase 0 computes masked scores into a VMEM
scratch (tracking the running row max), phase 1 normalizes against the
*final* max/denominator and accumulates probs @ V one page at a time, in
page order, one ``dot_general`` per page and head. Unlike one-pass
flash-style rescaling, the probabilities here are bit-identical to the
materialized-softmax reference (``_reference_attention`` /
``_mla_decode_absorbed``) before the optional ``probs_dtype`` cast, so
greedy decode tokens match the ``paged_gather`` path. ``score_dtype`` /
``probs_dtype`` reproduce the reference's intermediate casts (bf16 for GQA
attention; None = keep f32, the MLA absorbed path). K is read once per live
page in phase 0 and V once in phase 1; the MLA ``v=None`` path reads its
``ckv`` pages in both phases because keys and values share that storage.

Score scratch: token-major ``(n_groups * P * block_size, hb * G)`` f32. A
group's scores are rows ``[g*P*bs, (g+1)*P*bs)`` and a page's ``[j*bs,
(j+1)*bs)`` — sublane offsets, aligned whenever ``block_size`` is a multiple
of 8, which the TPU compiler needs (a lane offset of ``j*bs`` is not
provably 128-aligned and is refused). The denominator reduces the table's
``max_blocks * block_size`` rows over the leading axis, which on the CPU
backend sums in the reference's order. Each token pads ``hb * G`` (<= 128)
to a 128-lane row, so the scratch costs 512 B per token of ``max_len``. The
page buffers, two groups per page operand, come on top; the kernel asks
for the compiler's default 16 MiB of scoped VMEM plus what they and its
other blocks take, so the room left for the scratch is at least the
one-page-per-step kernel's: on a TPU v5e it compiles at ``max_len`` 32784
and is refused at 32800 (B=8, Hkv=8, G=4, D=64, block 16; one page per
step: 32720 and 32736; ``tests/test_tpu_compile.py``). Longer contexts
need the single-pass softmax of ROADMAP C2.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["paged_decode_attention", "pages_per_step", "heads_per_step",
           "copies_in_loop", "kernel_steps", "BIG_WINDOW"]

# matches the reference path's mask fill (jnp.finfo(f32).min, not -inf: a
# fully-masked row then softmaxes to uniform garbage instead of NaN)
NEG = float(jnp.finfo(jnp.float32).min)
BIG_WINDOW = 1 << 30              # "no window" sentinel (fits int32)
STEP_TOKENS = 128                 # tokens per KV head one step fetches
LANES = 128
SCOPED_VMEM = 16 << 20            # the TPU compiler's default scoped VMEM


def pages_per_step(block_size: int, n_pages: int) -> int:
    """P: the logical pages one step fetches and computes."""
    return max(1, min(n_pages, STEP_TOKENS // block_size))


def heads_per_step(n_kv_heads: int, group: int) -> int:
    """KV heads one grid cell handles: all of them while their query heads
    fit one lane row of the score scratch, else one."""
    return n_kv_heads if n_kv_heads * group <= LANES else 1


def copies_in_loop(widths) -> bool:
    """Whether the kernel copies pages itself in a loop over the live groups
    (pages of these widths fill whole 128-lane rows) rather than through
    the grid's pipeline (the compiler refuses a copy out of a slice of an
    HBM array whose rows it pads)."""
    return all(w % LANES == 0 for w in widths)


def kernel_steps(lengths, *, block_size: int, n_pages: int, widths,
                 window: Optional[int] = None) -> tuple:
    """``(steps, copy_steps, pages)`` of one kernel call per block of KV
    heads, for rows whose live lengths are ``lengths`` and pages of
    ``widths``: its steps over both phases (loop steps, and one for a row
    with no live group; or grid steps), those that copy at least one page
    (a group holding a live page, once per phase), and the live pages, each
    copied once for K and once for V."""
    P = pages_per_step(block_size, n_pages)
    span = P * block_size
    ln = np.asarray(lengths, np.int64)
    first = np.maximum(ln - (BIG_WINDOW if window is None else window), 0)
    groups = np.where(ln > 0, -(-ln // span) - first // span, 0)
    pages = np.where(ln > 0, -(-ln // block_size) - first // block_size, 0)
    copy_steps = int(2 * groups.sum())
    if copies_in_loop(widths):
        steps = copy_steps + int((groups == 0).sum())
    else:
        steps = 2 * len(ln) * -(-n_pages // P)
    return steps, copy_steps, int(pages.sum())


class _Cfg(NamedTuple):
    bs: int
    n_pages: int
    P: int
    hb: int
    G: int
    scale: float
    scale_mode: str
    score_dtype: object
    probs_dtype: object
    k_scale: float
    v_scale: float


def _page_live(pg, ln, win, c: _Cfg):
    # any key of this page both causally live and inside the window?
    start = pg * c.bs
    return (pg < c.n_pages) & (start < ln) & (start + c.bs > ln - win)


def _init(m_ref, s_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG)
    s_ref[...] = jnp.full_like(s_ref, NEG)    # unvisited pages stay masked
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _group_scores(g, keys, keys2, q_ref, q2_ref, s_ref, m_ref, ln, win,
                  c: _Cfg):
    """Masked scores of group ``g`` into its scratch rows. ``keys(h, j)``
    is page ``j``'s ``(bs, Dk)`` block of head ``h`` (``keys2`` the second
    score operand's, or None)."""
    span = c.P * c.bs
    q = q_ref[0, 0]                                       # (hb*G, Dk)
    head = jax.lax.broadcasted_iota(jnp.int32, (c.hb * c.G, span), 0)
    s = None
    for h in range(c.hb):
        k = jnp.concatenate([_dequant(keys(h, j), q.dtype, c.k_scale)
                             for j in range(c.P)])        # (span, Dk)
        sh = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if keys2 is not None:
            q2 = q2_ref[0, 0]
            k2 = jnp.concatenate([_dequant(keys2(h, j), q2.dtype, c.k_scale)
                                  for j in range(c.P)])
            sh = sh + jax.lax.dot_general(q2, k2, (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        # the rows of head h score against head h's keys
        s = sh if s is None else jnp.where(
            (head >= h * c.G) & (head < (h + 1) * c.G), sh, s)
    if c.score_dtype is not None:   # reference rounds scores (bf16 GQA)
        s = s.astype(c.score_dtype)
    s = s.astype(jnp.float32).T                          # (span, hb*G)
    s = s / c.scale if c.scale_mode == "div" else s * c.scale
    kpos = g * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
    s = jnp.where((kpos < ln) & (kpos > ln - 1 - win), s, NEG)
    s_ref[pl.ds(pl.multiple_of(g * span, span), span), :] = s
    m_ref[...] = jnp.maximum(m_ref[...], jnp.max(s, 0, keepdims=True))


def _denominator(s_ref, m_ref, l_ref, c: _Cfg):
    l_ref[...] = jnp.sum(jnp.exp(s_ref[:c.n_pages * c.bs, :] - m_ref[...]),
                         0, keepdims=True)


def _group_context(g, values, s_ref, m_ref, l_ref, acc_ref, ln, win,
                   c: _Cfg):
    """probs @ V over group ``g``'s live pages, one page at a time in page
    order; ``values(h, j)`` is page ``j``'s ``(bs, Dv)`` block of head
    ``h``."""
    for j in range(c.P):
        pg = g * c.P + j

        @pl.when(_page_live(pg, ln, win, c))
        def _():
            rows = pl.ds(pl.multiple_of(pg * c.bs, c.bs), c.bs)
            p = (jnp.exp(s_ref[rows, :] - m_ref[...])
                 / l_ref[...]).T                          # (hb*G, bs)
            for h in range(c.hb):
                ph = p[h * c.G:(h + 1) * c.G]
                if c.probs_dtype is not None:   # reference rounds probs
                    ph = ph.astype(c.probs_dtype)
                v = _dequant(values(h, j), ph.dtype, c.v_scale)
                acc_ref[h * c.G:(h + 1) * c.G] += jax.lax.dot_general(
                    ph, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)


def _unpack(rest, n_page_refs, has_k2, v_from_k):
    """(k, q2, k2, v, rest) from the kernel's refs after ``q``."""
    refs = list(rest)

    def take(n):
        out = refs[:n]
        del refs[:n]
        return out
    k = take(n_page_refs)
    q2, k2 = (take(1)[0], take(n_page_refs)) if has_k2 else (None, None)
    v = k if v_from_k else take(n_page_refs)
    return k, q2, k2, v, refs


def _grid_kernel(bt_ref, len_ref, win_ref, q_ref, *rest, c: _Cfg,
                 has_k2: bool, v_from_k: bool):
    """Grid ``(B, Hkv // hb, 2, n_groups)``: ``P`` BlockSpecs per page
    operand bring group ``g``'s pages; a step of a group with no live page
    runs nothing."""
    k_refs, q2_ref, k2_refs, v_refs, refs = _unpack(rest, c.P, has_k2,
                                                    v_from_k)
    o_ref, m_ref, l_ref, s_ref, acc_ref = refs
    b, ph, g = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    ln, win = len_ref[b], win_ref[0]
    span = c.P * c.bs
    group_live = ((ln > 0) & (g * span < ln)
                  & (g * span + span > jnp.maximum(ln - win, 0)))

    @pl.when((ph == 0) & (g == 0))
    def _():
        _init(m_ref, s_ref, acc_ref)

    @pl.when((ph == 0) & group_live)
    def _():
        _group_scores(g, lambda h, j: k_refs[j][0, h],
                      (lambda h, j: k2_refs[j][0, h]) if has_k2 else None,
                      q_ref, q2_ref, s_ref, m_ref, ln, win, c)

    @pl.when((ph == 1) & (g == 0))
    def _():
        _denominator(s_ref, m_ref, l_ref, c)

    @pl.when((ph == 1) & group_live)
    def _():
        _group_context(g, lambda h, j: v_refs[j][0, h], s_ref, m_ref, l_ref,
                       acc_ref, ln, win, c)

    @pl.when((ph == 1) & (g == pl.num_programs(3) - 1))
    def _():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def _loop_kernel(bt_ref, len_ref, win_ref, q_ref, *rest, c: _Cfg,
                 has_k2: bool, v_from_k: bool):
    """Grid ``(B, Hkv // hb)``: page operands stay in HBM, and each phase
    loops over the row's live groups, copying a group's live pages (one
    copy per page, every head of the cell) into one half of a double buffer
    while the other half computes."""
    (k_hbm,), q2_ref, k2_hbm, (v_hbm,), refs = _unpack(rest, 1, has_k2,
                                                       v_from_k)
    o_ref, k_buf, *refs = refs
    k2_buf = refs.pop(0) if has_k2 else None
    v_buf = k_buf if v_from_k else refs.pop(0)
    sem, m_ref, l_ref, s_ref, acc_ref = refs
    k_ops = [(k_hbm, k_buf)] + ([(k2_hbm[0], k2_buf)] if has_k2 else [])
    v_ops = [(v_hbm, v_buf)]

    b, hblk = pl.program_id(0), pl.program_id(1)
    ln, win = len_ref[b], win_ref[0]
    span = c.P * c.bs
    g_lo = jnp.maximum(ln - win, 0) // span
    n_grp = jnp.where(ln > 0, (ln + span - 1) // span - g_lo, 0)

    def group_copies(ops, g, slot, fn):
        for j in range(c.P):
            pg = g * c.P + j
            blk = jnp.maximum(bt_ref[b, jnp.minimum(pg, c.n_pages - 1)], 0)

            @pl.when(_page_live(pg, ln, win, c))
            def _():
                for src, buf in ops:
                    fn(pltpu.make_async_copy(
                        src.at[blk, pl.ds(hblk * c.hb, c.hb)],
                        buf.at[slot, :, j], sem.at[slot]))

    def start(ops, g, slot):
        group_copies(ops, g, slot, lambda cp: cp.start())

    def wait(ops, g, slot):
        group_copies(ops, g, slot, lambda cp: cp.wait())

    _init(m_ref, s_ref, acc_ref)

    @pl.when(n_grp > 0)
    def _():
        start(k_ops, g_lo, 0)

    def scores(i, carry):
        slot, g = i % 2, g_lo + i

        @pl.when(i + 1 < n_grp)
        def _():
            start(k_ops, g + 1, 1 - slot)

        @pl.when(i + 1 == n_grp)
        def _():
            start(v_ops, g_lo, 1 - slot)      # phase 1's first group

        wait(k_ops, g, slot)
        _group_scores(g, lambda h, j: k_buf[slot, h, j],
                      (lambda h, j: k2_buf[slot, h, j]) if has_k2 else None,
                      q_ref, q2_ref, s_ref, m_ref, ln, win, c)
        return carry

    jax.lax.fori_loop(0, n_grp, scores, 0)
    _denominator(s_ref, m_ref, l_ref, c)

    def context(i, carry):
        slot, g = (n_grp + i) % 2, g_lo + i

        @pl.when(i + 1 < n_grp)
        def _():
            start(v_ops, g + 1, 1 - slot)

        wait(v_ops, g, slot)
        _group_context(g, lambda h, j: v_buf[slot, h, j], s_ref, m_ref,
                       l_ref, acc_ref, ln, win, c)
        return carry

    jax.lax.fori_loop(0, n_grp, context, 0)
    o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def _dequant(x: jax.Array, dtype, scale: float) -> jax.Array:
    """In-register dequant of a (possibly fp8) KV block. ``scale`` is the
    per-tensor dequant multiplier (scale_inv); 1.0 skips the multiply so the
    unscaled path stays bit-identical to ``paged_gather``'s plain upcast."""
    if scale == 1.0:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * scale).astype(dtype)


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of a buffer whose last two dims pad to whole tiles."""
    item = jnp.dtype(dtype).itemsize
    sub = 32 // item                      # sublanes of a tile: 8 rows of f32
    *lead, rows, cols = shape
    return (int(np.prod(lead)) * -(-rows // sub) * sub
            * -(-cols // LANES) * LANES * item)


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
    c = _Cfg(bs=bs, n_pages=n_pages, P=pages_per_step(bs, n_pages),
             hb=heads_per_step(Hkv, G), G=G, scale=scale,
             scale_mode=scale_mode, score_dtype=score_dtype,
             probs_dtype=probs_dtype, k_scale=k_scale, v_scale=v_scale)
    P, hb = c.P, c.hb
    n_hblk = Hkv // hb
    n_groups = pl.cdiv(n_pages, P)
    span = P * bs
    pages = [k] + ([k2] if has_k2 else []) + ([] if v_from_k else [v])
    looped = copies_in_loop(x.shape[-1] for x in pages)

    def q_block(x):
        # (B, Hkv, G, D) -> (B, cells, hb*G, D): a cell's query heads as rows
        return (x.reshape(B, n_hblk, hb * G, x.shape[-1]),
                pl.BlockSpec((1, 1, hb * G, x.shape[-1]),
                             lambda b, h, *_: (b, h, 0, 0)))

    def page_specs(x, keep0, keep1):
        if looped:                    # stays in HBM; the kernel copies it
            return [x], [pl.BlockSpec(memory_space=pl.ANY)]

        # page j of group g, every head of the cell; dead pages, and phases
        # that don't consume the operand, map to the trash block 0 (a
        # revisited block's copy is elided)
        def spec(j):
            def index(b, h, ph, g, bt, ln, wn):
                pg = g * P + j
                live = ((pg < n_pages) & (pg * bs < ln[b])
                        & (pg * bs + bs > ln[b] - wn[0])
                        & ((ph == 0) & keep0 | (ph == 1) & keep1))
                blk = bt[b, jnp.minimum(pg, n_pages - 1)]
                return (jnp.where(live, jnp.maximum(blk, 0), 0), h, 0, 0)
            return pl.BlockSpec((1, hb, bs, x.shape[-1]), index)
        return [x] * P, [spec(j) for j in range(P)]

    operands, in_specs = [], []
    for x, keep0, keep1, qx in ((k, True, v_from_k, q), (k2, True, False, q2),
                                (v, False, True, None)):
        if x is None:
            continue
        if qx is not None:
            xr, x_spec = q_block(qx)
            operands.append(xr)
            in_specs.append(x_spec)
        ops, specs = page_specs(x, keep0, keep1)
        operands += ops
        in_specs += specs
    bufs = []
    if looped:   # a double buffer of P pages per operand, and its semaphores
        bufs = [pltpu.VMEM((2, hb, P, bs, x.shape[-1]), x.dtype)
                for x in pages] + [pltpu.SemaphoreType.DMA((2,))]
    # the default scoped VMEM plus the double-buffered blocks or pages, the
    # accumulator and the score rows the last group pads: the score scratch
    # keeps the room it had with one page per grid step
    queries = [q, q2] if has_k2 else [q]
    blocks = [((hb * G, x.shape[-1]), x.dtype) for x in queries]
    blocks += [((hb * G, Dv), out_dtype)]
    blocks += [((hb, bs, x.shape[-1]), x.dtype) for x in pages] * P
    vmem = (SCOPED_VMEM + 2 * sum(_vmem_bytes(*b) for b in blocks)
            + _vmem_bytes((hb * G, Dv), jnp.float32)
            + _vmem_bytes((n_groups * span - n_pages * bs, hb * G),
                          jnp.float32))

    grid = (B, n_hblk) if looped else (B, n_hblk, 2, n_groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hb * G, Dv),
                               lambda b, h, *_: (b, h, 0, 0)),
        scratch_shapes=bufs + [
            pltpu.VMEM((1, hb * G), jnp.float32),           # running max
            pltpu.VMEM((1, hb * G), jnp.float32),           # denominator
            pltpu.VMEM((n_groups * span, hb * G), jnp.float32),  # scores
            pltpu.VMEM((hb * G, Dv), jnp.float32),          # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_loop_kernel if looped else _grid_kernel, c=c,
                          has_k2=has_k2, v_from_k=v_from_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_hblk, hb * G, Dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 2 + ("arbitrary",) * (
                len(grid) - 2),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(block_tables, lengths, window, *operands)
    return out.reshape(B, Hkv, G, Dv)


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
