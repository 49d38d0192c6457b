"""Functional NN layers (norms, rope, MLP, attention) with spec builders.

Conventions:
* params are nested dicts; spec builders return flat ``path -> ParamSpec``.
* every quantizable matmul goes through ``repro.quant.qops`` with an op name
  equal to its param-path prefix (e.g. ``layers/3/attn/q_proj``), so the MP
  pipeline, the partitioner and the param tree share one namespace.
* weights are stored (out_features, in_features) following eq. (8) of the
  paper: ``y = x @ w^T + b``.

KV cache — two layouts share the attention math:

* dense ring (the default / one-shot path): ``{"k": (B,W,Hkv,D), "v": ...,
  "pos": (B,W)}`` where ``pos`` holds the absolute position stored in each
  slot (-1 = empty). ``W = min(max_len, window)`` — sliding-window archs get
  O(window) decode memory (what makes hymba ``long_500k`` deployable);
  full-attention archs use W = max_len where the ring write degenerates to
  an append.
* paged blocks (continuous serving): ``{"k": (n_blocks, Hkv, block_size,
  D), "v": ...}`` — head-major physical blocks owned by a
  ``PagedCachePool`` (MLA latents use one head: ``(n_blocks, 1,
  block_size, width)``; every page leaf has this 4-D layout); each
  decode row carries a block table (row of physical block ids, -1 =
  unallocated) and logical position ``j*block_size + i`` lives at page-table
  entry ``j``, offset ``i``. There is no ``pos`` leaf: the pool guarantees
  every block a row's table maps is written contiguously up to the row's
  position, so every key at logical position <= the query position is fresh
  by construction and the causal mask alone separates live keys from stale
  block contents. Writes stay single-owner: a block referenced by several
  tables (prefix caching) is read-shared only — the pool copy-on-write
  forks it before any chunk would write into it, and decode never writes a
  shared page (its write range starts past the matched prefix). Block 0
  is a trash block (never allocated) that absorbs writes from vacant decode
  rows, whose block tables are all -1. Blocks are written one token per
  decode step (``paged_write``) or a whole prefill chunk at a time
  (``paged_write_chunk`` — the "paged prefill" path, which also routes
  bucket-padding writes to the trash block).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.nn.spec import ParamSpec
from repro.quant import qops
from repro.quant.qops import QuantContext

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_specs(prefix: str, dim: int, kind: str = "rmsnorm") -> dict:
    specs = {f"{prefix}/scale": ParamSpec((dim,), ("embed",), jnp.float32, "ones")}
    if kind == "layernorm":
        specs[f"{prefix}/bias"] = ParamSpec((dim,), ("embed",), jnp.float32, "zeros")
    return specs


def apply_norm(p: dict, x: jax.Array, kind: str = "rmsnorm",
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        raise ValueError(kind)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_table(positions: jax.Array, d_head: int, theta: float) -> tuple:
    """positions: (..., T) int32 -> (sin, cos) of shape (..., T, d_head//2)."""
    half = d_head // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, T, H, D); sin/cos: (B, T, D//2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s = sin[..., None, :].astype(jnp.float32)
    c = cos[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu2":  # squared ReLU (Nemotron-4 / Primer)
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def mlp_specs(prefix: str, d_model: int, d_ff: int, activation: str,
              bias: bool = False) -> dict:
    specs = {}
    if activation == "swiglu":
        specs[f"{prefix}/gate_proj/w"] = ParamSpec((d_ff, d_model), ("ffn", "embed"),
                                                   init="scaled_normal")
    specs[f"{prefix}/up_proj/w"] = ParamSpec((d_ff, d_model), ("ffn", "embed"),
                                             init="scaled_normal")
    specs[f"{prefix}/down_proj/w"] = ParamSpec((d_model, d_ff), ("embed", "ffn"),
                                               init="scaled_normal")
    if bias:
        specs[f"{prefix}/up_proj/b"] = ParamSpec((d_ff,), ("ffn",), init="zeros")
        specs[f"{prefix}/down_proj/b"] = ParamSpec((d_model,), ("embed",), init="zeros")
    return specs


def apply_mlp(p: dict, ctx: QuantContext, scope: str, x: jax.Array,
              activation: str) -> jax.Array:
    if activation == "swiglu":
        g = qops.linear(ctx, f"{scope}/gate_proj", x, p["gate_proj"]["w"])
        u = qops.linear(ctx, f"{scope}/up_proj", x, p["up_proj"]["w"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = qops.linear(ctx, f"{scope}/up_proj", x, p["up_proj"]["w"],
                        p["up_proj"].get("b"))
        h = _act(activation, u.astype(jnp.float32)).astype(x.dtype)
    return qops.linear(ctx, f"{scope}/down_proj", h, p["down_proj"]["w"],
                       p["down_proj"].get("b"))


# ---------------------------------------------------------------------------
# Attention (GQA, optional bias / sliding window / cross-attention)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qkv_bias: bool = False
    causal: bool = True
    rope_theta: Optional[float] = 10000.0   # None => NoPE (e.g. cross-attn)
    window: Optional[int] = None            # sliding-window size
    flash_min_seq: int = 4096               # blocked attention above this q_len
    flash_block: int = 1024
    # per-tensor dequant multipliers for *paged* KV-cache reads (e.g. an fp8
    # cache carrying a calibration scale): ((cache_entry, scale), ...) pairs
    # — a tuple, not a dict, so the frozen config stays hashable. One source
    # of truth for both paged read paths: the fused kernel dequantizes
    # in-register and the gather fallback applies the identical
    # f32-multiply-then-cast, so greedy tokens cannot depend on which path a
    # layer takes. Unit scales cost nothing on either path.
    kv_dequant_scales: Optional[tuple] = None


def attn_specs(prefix: str, cfg: AttnConfig) -> dict:
    dm, H, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    specs = {
        f"{prefix}/q_proj/w": ParamSpec((H * D, dm), ("heads", "embed"),
                                        init="scaled_normal"),
        f"{prefix}/k_proj/w": ParamSpec((Hkv * D, dm), ("heads", "embed"),
                                        init="scaled_normal"),
        f"{prefix}/v_proj/w": ParamSpec((Hkv * D, dm), ("heads", "embed"),
                                        init="scaled_normal"),
        f"{prefix}/o_proj/w": ParamSpec((dm, H * D), ("embed", "heads"),
                                        init="scaled_normal"),
    }
    if cfg.qkv_bias:
        for n, width in (("q_proj", H * D), ("k_proj", Hkv * D), ("v_proj", Hkv * D)):
            specs[f"{prefix}/{n}/b"] = ParamSpec((width,), ("heads",), init="zeros")
    return specs


def kv_cache_spec(cfg: AttnConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16, ring: bool = True,
                  chunk_extra: int = 0) -> dict:
    """``ring=False`` disables the sliding-window ring clamp and keeps the
    full ``max_len`` layout (positions stay contiguous from slot 0) — the
    shape ``LM.paged_insert`` needs to reshape a prefill cache into blocks;
    the window is still enforced by the attention mask.

    ``chunk_extra`` widens windowed rings to ``window + chunk_extra`` rows:
    a chunked prefill writes up to a whole chunk past the window before the
    chunk's earliest query attends, so a ring clamped exactly at ``window``
    would overwrite keys still inside that query's window whenever
    ``window`` is not chunk-aligned. Engines serving dense chunked prefill
    pass their ``chunk_len`` here; decode and one-shot prefill need no
    slack."""
    W = (max_len if (cfg.window is None or not ring)
         else min(max_len, cfg.window + chunk_extra))
    # kv_heads shard over 'model' when divisible; otherwise head_dim picks up
    # the model axis (contraction-dim sharding -> small score all-reduce)
    return {
        "k": ParamSpec((batch, W, cfg.n_kv_heads, cfg.d_head),
                       ("act_batch", None, "kv_heads", "head_dim"), dtype,
                       "zeros"),
        "v": ParamSpec((batch, W, cfg.n_kv_heads, cfg.d_head),
                       ("act_batch", None, "kv_heads", "head_dim"), dtype,
                       "zeros"),
        "pos": ParamSpec((batch, W), ("act_batch", None), jnp.int32, "zeros"),
    }


def kv_page_spec(cfg: AttnConfig, n_blocks: int, block_size: int,
                 dtype=jnp.bfloat16) -> dict:
    """Paged KV storage: ``n_blocks`` physical blocks of ``block_size``
    tokens, shared by all decode rows via block tables. Sliding-window archs
    keep masked-window *compute* but not O(window) *memory* under paging
    (block tables grow with absolute position).

    The leading block dim carries the ``kv_blocks`` logical axis: under a
    serving mesh the physical pool is device-sharded over ``data`` (each
    shard owns a contiguous page range, see ``PagedCachePool``), falling
    back to replication when ``n_blocks`` doesn't divide. Pages are
    head-major ``(n_blocks, Hkv, block_size, D)``: the fused kernel fetches
    one head's page as a ``(block_size, D)`` tile."""
    return {
        "k": ParamSpec((n_blocks, cfg.n_kv_heads, block_size, cfg.d_head),
                       ("kv_blocks", "kv_heads", None, "head_dim"), dtype,
                       "zeros"),
        "v": ParamSpec((n_blocks, cfg.n_kv_heads, block_size, cfg.d_head),
                       ("kv_blocks", "kv_heads", None, "head_dim"), dtype,
                       "zeros"),
    }


def paged_write_chunk(cache: dict, tensors: dict, block_tables: jax.Array,
                      positions: jax.Array, valid: jax.Array) -> dict:
    """Scatter a whole prefill chunk into each row's physical blocks (the
    "paged prefill" path: blocks are written directly, no dense-then-scatter).

    ``positions``: (B, T) absolute positions of the chunk's tokens;
    ``valid``: (B, T) bool — padded tail entries and vacant rows are routed
    to the trash block 0, as are positions whose page is unallocated (-1).
    Valid entries land at unique (page, offset) pairs because the pool
    keeps every written block single-writer (shared prefix pages are
    copy-on-write forked before they enter any write range) and writes
    contiguously. Chunks may start mid-sequence against a pre-populated
    table — resumed prefills and prefix-cache tail chunks rely on this.
    ``tensors`` are (B, T, heads, width), matching the head-major pages.
    """
    bs = next(iter(cache.values())).shape[2]
    nb = block_tables.shape[1]
    page_idx = jnp.clip(positions // bs, 0, nb - 1)
    page = jnp.take_along_axis(block_tables, page_idx, axis=1)     # (B, T)
    page = jnp.maximum(jnp.where(valid, page, -1), 0)
    off = positions % bs
    new = dict(cache)
    for name, t in tensors.items():
        new[name] = cache[name].at[page, :, off].set(
            t.astype(cache[name].dtype))
    return new


def paged_write(cache: dict, tensors: dict, block_tables: jax.Array,
                cache_pos: jax.Array) -> dict:
    """Scatter one new token per decode row into its physical block.

    ``block_tables``: (B, max_blocks) int32 physical block ids; ``cache_pos``:
    (B,) absolute write positions. Rows with an unallocated page (table entry
    -1, e.g. vacant slots) are clamped to the trash block 0.
    """
    bs = next(iter(cache.values())).shape[2]
    B = block_tables.shape[0]
    cp = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (B,))
    page = jnp.take_along_axis(block_tables, (cp // bs)[:, None], axis=1)[:, 0]
    page = jnp.maximum(page, 0)              # -1 (vacant/unallocated) -> trash
    off = cp % bs
    new = dict(cache)
    for name, t in tensors.items():
        new[name] = cache[name].at[page, :, off].set(
            t[:, 0].astype(cache[name].dtype))
    return new


def paged_gather(cache: dict, block_tables: jax.Array, dtype,
                 scales: Optional[dict] = None) -> tuple:
    """Gather each row's blocks into logical order: (B, S, ...) tensors plus
    the (B, S) logical key positions (S = max_blocks * block_size); each
    (n_blocks, heads, block_size, width) leaf comes back as
    (B, S, heads, width). Entries beyond a row's written length read
    stale/trash data; they sit at logical positions > the row's query
    position, so the causal mask removes them.

    ``scales`` maps cache-entry names to per-tensor dequant multipliers,
    applied with exactly the fused kernel's ``_dequant`` semantics (f32
    multiply, cast to ``dtype``; a 1.0 scale is a plain upcast so the
    unscaled path stays bit-identical to the legacy gather)."""
    bs = next(iter(cache.values())).shape[2]
    B, nb = block_tables.shape
    bt = jnp.maximum(block_tables, 0)

    def deq(name, arr):
        g = jnp.take(arr, bt, axis=0).swapaxes(2, 3)   # (B, nb, bs, H, D)
        g = g.reshape(B, nb * bs, arr.shape[1], arr.shape[3])
        s = 1.0 if scales is None else float(scales.get(name, 1.0))
        if s == 1.0:
            return g.astype(dtype)
        return (g.astype(jnp.float32) * s).astype(dtype)

    out = {name: deq(name, arr) for name, arr in cache.items()}
    kp = jnp.broadcast_to(jnp.arange(nb * bs, dtype=jnp.int32)[None], (B, nb * bs))
    return out, kp


def use_fused_paged(ctx: QuantContext, scope: str, paged_attn: str) -> bool:
    """THE paged-decode kernel switch: every call site (attention and MLA)
    funnels through this one predicate, so gather-vs-fused policy lives in
    exactly one place.

    The fused kernel replaces the reference path's two quantizable BGEMMs
    (``qk_matmul`` / ``av_matmul``) with in-kernel math, so it only serves
    layers where those ops run at full precision; a layer whose attention
    BGEMMs carry an MP format keeps the gather path and its exact
    quantization semantics. Probe mode and op-inventory traces also need the
    ``qops`` entry points (probe injection / OpInfo registration), so they
    stay on the reference path too.
    """
    assert paged_attn in ("fused", "gather"), paged_attn
    if paged_attn != "fused":
        return False
    if ctx.mode == "probe" or ctx.registry is not None:
        return False
    if ctx.mode == "mp":
        from repro.quant.formats import get_format
        for op in ("qk_matmul", "av_matmul"):
            if get_format(ctx.format_for(f"{scope}/{op}")).is_quantized:
                return False
    return True


def _mesh_fused_ok(batch: int, n_kv_heads: int) -> bool:
    """Mesh leg of the fused-paged dispatch: under a serving mesh the kernel
    runs per-shard (shard_map), which needs the decode batch to divide the
    ``data`` axis and the KV heads to divide ``model``; otherwise the layer
    takes the gather path, which GSPMD partitions correctly (and which is
    bit-identical to the kernel, so greedy parity holds either way)."""
    from repro.distributed.sharding import current_serving_layout
    layout = current_serving_layout()
    return layout is None or layout.fused_ok(batch, n_kv_heads)


def _paged_kernel_call(qk: jax.Array, k_pages: jax.Array, v_pages,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       window=None, q2=None, k2=None, **kw) -> jax.Array:
    """Invoke the Pallas paged-decode kernel — per-shard under ``shard_map``
    when a serving mesh layout is active.

    Per shard the operands are: decode rows split over ``data`` (each shard
    sees its own slots' queries/lengths/block-table rows), KV heads split
    over ``model``, and — when the pool is page-sharded — the block dim
    split over ``data`` with global block ids translated to shard-local ones
    (slot ``s``'s blocks live in ``s``'s shard by pool construction; -1
    stays -1 and clamps to the shard's own trash block). Each per-shard grid
    keeps exactly the single-device kernel's per-row summation order, so
    sharded decode is bit-identical to the single-device engine."""
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.distributed.sharding import current_serving_layout
    layout = current_serving_layout()
    if layout is None or (layout.data == 1 and layout.model == 1):
        return paged_decode_attention(qk, k_pages, v_pages, block_tables,
                                      lengths, window=window, q2=q2, k2=k2,
                                      **kw)
    from jax.sharding import PartitionSpec as P

    q_spec = P("data", "model", None, None)
    page_spec = P("data" if layout.shard_pages else None, "model", None, None)
    operands = [qk, k_pages, block_tables, lengths]
    specs = [q_spec, page_spec, P("data", None), P("data")]
    has_v = v_pages is not None
    if has_v:
        operands.append(v_pages)
        specs.append(page_spec)
    traced_window = window is not None and not isinstance(window, int)
    if traced_window:
        operands.append(window)
        specs.append(P())
    has_q2 = q2 is not None
    if has_q2:
        operands.extend([q2, k2])
        specs.extend([q_spec, page_spec])
    bps = layout.blocks_per_shard

    def body(qk_, pages_, bt_, len_, *rest):
        rest = list(rest)
        vp = rest.pop(0) if has_v else None
        w = rest.pop(0) if traced_window else window
        q2_, k2_ = rest if has_q2 else (None, None)
        if layout.shard_pages:
            off = jax.lax.axis_index("data") * bps
            bt_ = jnp.where(bt_ >= 0, bt_ - off, bt_)
        return paged_decode_attention(qk_, pages_, vp, bt_, len_, window=w,
                                      q2=q2_, k2=k2_, **kw)

    return jax.shard_map(body, mesh=layout.mesh, in_specs=tuple(specs),
                         out_specs=P("data", "model", None, None),
                         check_vma=False)(*operands)


def paged_update_attend(cache: dict, tensors: dict, block_tables: jax.Array,
                        positions: jax.Array, cache_pos, chunk_valid,
                        dtype, *, fused: bool,
                        scales: Optional[dict] = None) -> tuple:
    """Single entry point for every paged-cache attention interaction.

    Writes the fresh K/V — one decode token (``cache_pos``) or a whole
    prefill chunk (``chunk_valid``) — into physical blocks, then either
    gathers the logical ``(B, S)`` layout (returns ``(new_cache, g, kp)``)
    or, for a fused decode step, returns ``(new_cache, None, None)`` so the
    caller attends block-major KV in place via the Pallas kernel. The
    chunked-prefill continuation always gathers: its multi-token queries
    must attend every earlier chunk through the logical layout.

    ``scales`` (per-entry dequant multipliers) reaches the gather through
    :func:`paged_gather`; callers taking the fused return must hand the
    *same* mapping to the kernel so both read paths dequantize identically.
    The same mapping also drives the *write* side: fresh K/V is divided by
    its entry's scale in f32 before the storage-dtype cast, so a
    calibrated per-layer scale maps each entry's amax into the fp8
    representable range (read paths multiply it back). Unit scales skip the
    divide entirely — the default path stays bit-identical.

    fp8 storage saturates: values beyond the format's finite max are
    clamped to it before the cast (e4m3fn has no inf — an overflow would
    otherwise store NaN and poison every later attention read over that
    block). In-range values are untouched, so sub-amax traffic stays
    bit-identical; a calibrated scale moves the whole range in-bounds and
    the clamp never fires.
    """
    if scales:
        tensors = {
            name: (t if float(scales.get(name, 1.0)) == 1.0
                   else t.astype(jnp.float32) / float(scales[name]))
            for name, t in tensors.items()}

    def _saturate(name, t):
        cd = cache[name].dtype
        if cd.itemsize == 1 and jnp.issubdtype(cd, jnp.floating):
            fmax = float(jnp.finfo(cd).max)
            return jnp.clip(t.astype(jnp.float32), -fmax, fmax)
        return t

    tensors = {name: _saturate(name, t) for name, t in tensors.items()}
    if chunk_valid is not None:
        new_cache = paged_write_chunk(cache, tensors, block_tables,
                                      positions, chunk_valid)
    else:
        assert cache_pos is not None, "paged attention is decode-only"
        new_cache = paged_write(cache, tensors, block_tables, cache_pos)
        if fused:
            return new_cache, None, None
    g, kp = paged_gather(new_cache, block_tables, dtype, scales)
    return new_cache, g, kp


def _fused_paged_attention(cfg: AttnConfig, q: jax.Array, cache: dict,
                           block_tables: jax.Array, positions: jax.Array,
                           window, scales: Optional[dict] = None) -> jax.Array:
    """GQA decode against block-major K/V: one kernel call per layer, no
    ``(B, S)`` gather. ``window`` may be None, int, or a traced scalar
    (scan-mode per-layer windows). ``scales`` carries the same per-entry
    dequant multipliers the gather fallback applies, handed to the kernel
    as its in-register ``k_scale``/``v_scale``. Returns (B, 1, H, Dv)."""
    B, T, H, D = q.shape
    assert T == 1, "fused paged attention is single-query decode"
    Hkv = cfg.n_kv_heads
    qk = q.reshape(B, Hkv, H // Hkv, D)
    lengths = positions[:, 0] + 1
    sc = scales or {}
    o = _paged_kernel_call(
        qk, cache["k"], cache["v"], block_tables, lengths, window=window,
        scale=math.sqrt(D), scale_mode="div", score_dtype=q.dtype,
        probs_dtype=q.dtype, k_scale=float(sc.get("k", 1.0)),
        v_scale=float(sc.get("v", 1.0)), out_dtype=q.dtype)
    return o.reshape(B, 1, H, o.shape[-1])


def _split_heads(x: jax.Array, n: int, d: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, d)


def _cache_roundtrip(t: jax.Array, cache_leaf: jax.Array, dtype) -> jax.Array:
    """Pass fresh prefill K/V through the cache storage dtype before
    attending, so prefill attention sees exactly the values every later read
    of the cache sees (fp8 caches: the first token is computed from
    fp8-rounded K/V — the invariant that makes chunked prefill, which attends
    *through* the cache, bit-identical to one-shot prefill)."""
    if cache_leaf.dtype == t.dtype:
        return t
    return t.astype(cache_leaf.dtype).astype(dtype)


def _cache_write_chunk(cache: dict, tensors: dict, positions: jax.Array,
                       valid: jax.Array, start: jax.Array) -> dict:
    """Masked bucketed-prefill write into the dense ring.

    ``positions``: (B, T) absolute positions (``start[:, None] + arange``);
    ``valid``: (B, T) bool marking real tokens (padding sits at the tail);
    ``start``: (B,) — rows with start == 0 get their ``pos`` ring reset to -1
    first (slot reuse must not leak the previous occupant's keys), rows with
    no valid entries (co-batched decoding/vacant slots) are left untouched.
    Ring semantics: only each row's last W valid entries are kept.
    """
    B, T = positions.shape
    W = cache["pos"].shape[1]
    end = start + jnp.sum(valid, axis=1).astype(jnp.int32)         # (B,)
    keep = valid & (positions >= (end - W)[:, None])
    slot = jnp.where(keep, positions % W, W)         # W = out of bounds: drop
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    new = dict(cache)
    pos0 = jnp.where((start == 0)[:, None], -1, cache["pos"])
    new["pos"] = pos0.at[bidx, slot].set(positions.astype(jnp.int32),
                                         mode="drop")
    for name, t in tensors.items():
        new[name] = cache[name].at[bidx, slot].set(
            t.astype(cache[name].dtype), mode="drop")
    return new


def _ring_logical_gather(cache: dict, names: tuple, dtype,
                         start: jax.Array, valid: jax.Array) -> tuple:
    """Gather a dense ring into ascending *logical* order for chunked-prefill
    continuation: after a chunk ending at absolute position ``end`` lands,
    the ring holds exactly positions ``end - W .. end - 1`` (contiguous
    writes from 0), so logical position ``p`` lives at slot ``p % W``.
    Returns ``({name: (B, W, ...) gathered}, kp)`` with ``kp[b, j] =
    end_b - W + j`` — entries with ``kp < 0`` (ring not yet full) gather
    arbitrary slots and are excluded by ``_mask_from_pos``'s ``k_pos >= 0``
    clause, contributing exact zeros; valid keys appear in the same
    ascending order a one-shot prefill attends them."""
    W = cache["pos"].shape[1]
    end = start + jnp.sum(valid, axis=1).astype(jnp.int32)          # (B,)
    kp = end[:, None] + jnp.arange(-W, 0, dtype=jnp.int32)[None]    # (B, W)
    slot = kp % W                                     # nonneg for kp < 0 too
    bidx = jnp.broadcast_to(jnp.arange(slot.shape[0])[:, None], slot.shape)
    out = {name: cache[name][bidx, slot].astype(dtype) for name in names}
    return out, kp


def _cache_write(cache: dict, tensors: dict, positions: jax.Array,
                 cache_pos: Optional[jax.Array]) -> dict:
    """Write T new entries into the ring buffer. positions: (B, T)."""
    first = next(iter(tensors.values()))
    B, T = first.shape[0], first.shape[1]
    W = cache["pos"].shape[1]
    new = dict(cache)
    if cache_pos is None and T <= W:
        # prefill, fits: contiguous write at slot 0
        for name, t in tensors.items():
            new[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], t.astype(cache[name].dtype), 0, axis=1)
        pos_fill = jnp.full((B, W), -1, jnp.int32)
        new["pos"] = jax.lax.dynamic_update_slice_in_dim(
            pos_fill, positions.astype(jnp.int32), 0, axis=1)
    elif cache_pos is None:
        # prefill longer than the window: keep the last W entries
        idx = (positions[0, T - W:] % W).astype(jnp.int32)
        for name, t in tensors.items():
            new[name] = cache[name].at[:, idx].set(
                t[:, T - W:].astype(cache[name].dtype))
        new["pos"] = cache["pos"].at[:, idx].set(positions[:, T - W:])
    elif getattr(cache_pos, "ndim", 0) == 1:
        # decode with per-sequence positions (continuous batching): each
        # batch row writes its own ring slot
        slot = (cache_pos % W).astype(jnp.int32)          # (B,)
        bidx = jnp.arange(B)
        for name, t in tensors.items():
            new[name] = cache[name].at[bidx, slot].set(
                t[:, 0].astype(cache[name].dtype))
        new["pos"] = cache["pos"].at[bidx, slot].set(
            positions[:, 0].astype(jnp.int32))
    else:
        # decode: single-slot ring write
        slot = (cache_pos % W).astype(jnp.int32)
        for name, t in tensors.items():
            new[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], t.astype(cache[name].dtype), slot, axis=1)
        new["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions.astype(jnp.int32), slot, axis=1)
    return new


def _mask_from_pos(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
                   window, valid: Optional[jax.Array]) -> jax.Array:
    """(B, Tq, Tk) boolean mask. window may be None, int, or traced scalar."""
    m = k_pos[:, None, :] >= 0
    if causal:
        m &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        m &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    if valid is not None:
        m &= valid[:, None, :]
    return m


def attention(p: dict, ctx: QuantContext, scope: str, cfg: AttnConfig,
              x: jax.Array, positions: jax.Array, *,
              kv_x: Optional[jax.Array] = None,
              kv_positions: Optional[jax.Array] = None,
              kv_valid: Optional[jax.Array] = None,
              cache: Optional[dict] = None,
              cache_pos: Optional[jax.Array] = None,
              block_tables: Optional[jax.Array] = None,
              chunk_valid: Optional[jax.Array] = None,
              chunk_start: Optional[jax.Array] = None,
              chunk_ring: bool = False,
              window: Union[None, int, jax.Array] = "cfg",
              cross: bool = False, paged_attn: str = "fused"):
    """Returns (y, new_cache).

    * self-attention:  default. K/V come from ``x`` and are written into
      ``cache`` when given (prefill: cache_pos None; decode: scalar pos).
    * paged decode: ``block_tables`` given with a block-major ``cache`` —
      the new token is scattered into its row's page and, with
      ``paged_attn="fused"`` (the default), attended *in place* by the
      Pallas paged-attention kernel (block-table indirection in-kernel, HBM
      traffic proportional to live tokens). ``paged_attn="gather"`` keeps
      the reference path: K/V gathered back into logical ``(B, S)`` order
      before the (identical) attention math. Layers whose attention BGEMMs
      carry an MP format, probe/registry traces, and chunked-prefill
      continuation always take the gather path (see
      :func:`use_fused_paged`).
    * chunked/bucketed prefill: ``chunk_valid`` (B, T) marks real tokens in
      a padded chunk starting at ``chunk_start`` (B,). Paged: the chunk is
      written straight into physical blocks and attention runs over the
      gathered logical layout (so a continuation chunk sees every earlier
      chunk's keys). Dense: masked ring write + local attention by default
      (single-shot bucketed prefill — continuation-blind);
      ``chunk_ring=True`` instead attends the whole ring gathered into
      logical order, so dense engines can split prompts into chunks too —
      windowed archs additionally need their ring widened to
      ``window + chunk_len`` (``kv_cache_spec(chunk_extra=...)``) or a
      chunk write past an unaligned window boundary evicts keys the
      chunk's earliest query still needs.
    * cross-attention: ``cross=True``; K/V from ``kv_x`` (encoder output) or
      from a pre-computed ``cache`` {"k","v"}; bidirectional, no RoPE.
    * ``window``: "cfg" -> use cfg.window; else override (may be traced).
    """
    B, T, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if isinstance(window, str) and window == "cfg":
        window = cfg.window

    q = qops.linear(ctx, f"{scope}/q_proj", x, p["q_proj"]["w"],
                    p["q_proj"].get("b"))
    q = _split_heads(q, H, D)

    new_cache = cache
    causal = cfg.causal
    y_fused = None
    if cross:
        causal = False
        if kv_x is not None:
            k = _split_heads(qops.linear(ctx, f"{scope}/k_proj", kv_x,
                                         p["k_proj"]["w"], p["k_proj"].get("b")),
                             Hkv, D)
            v = _split_heads(qops.linear(ctx, f"{scope}/v_proj", kv_x,
                                         p["v_proj"]["w"], p["v_proj"].get("b")),
                             Hkv, D)
        else:  # pre-computed encoder K/V (decode)
            k, v = cache["k"], cache["v"]
        S = k.shape[1]
        kp = kv_positions if kv_positions is not None else jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        mask = _mask_from_pos(jnp.maximum(positions, 0), kp, False, None, kv_valid)
    else:
        # ---- self-attention ----
        k = _split_heads(qops.linear(ctx, f"{scope}/k_proj", x,
                                     p["k_proj"]["w"], p["k_proj"].get("b")), Hkv, D)
        v = _split_heads(qops.linear(ctx, f"{scope}/v_proj", x,
                                     p["v_proj"]["w"], p["v_proj"].get("b")), Hkv, D)
        if cfg.rope_theta is not None:
            sin, cos = rope_table(positions, D, cfg.rope_theta)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        if cache is not None and block_tables is not None:
            # paged: a prefill chunk or decode token written straight into
            # physical blocks. Decode attends them in place via the fused
            # kernel when eligible; chunk continuation (and the gather
            # fallback) attends the gathered logical layout, so a
            # continuation chunk sees every earlier chunk's keys.
            fused = (chunk_valid is None and causal
                     and use_fused_paged(ctx, scope, paged_attn)
                     and _mesh_fused_ok(B, Hkv))
            # one mapping feeds both read paths: the kernel's in-register
            # dequant and the gather fallback can never disagree on scales
            kv_scales = dict(cfg.kv_dequant_scales or ())
            new_cache, g, kp = paged_update_attend(
                cache, {"k": k, "v": v}, block_tables, positions, cache_pos,
                chunk_valid, x.dtype, fused=fused, scales=kv_scales)
            if g is None:
                y_fused = _fused_paged_attention(cfg, q, new_cache,
                                                 block_tables, positions,
                                                 window, scales=kv_scales)
            else:
                k, v = g["k"], g["v"]
        elif cache is not None and chunk_valid is not None:
            # dense bucketed prefill: masked ring write, then either local
            # attention over the cache-dtype-rounded fresh K/V (single-shot
            # buckets, flash-capable) or — for chunked continuation
            # (chunk_ring) — attention over the whole ring gathered into
            # logical order, so this chunk's queries see every earlier
            # chunk's keys exactly as later cache reads will
            new_cache = _cache_write_chunk(cache, {"k": k, "v": v},
                                           positions, chunk_valid,
                                           chunk_start)
            if chunk_ring:
                g, kp = _ring_logical_gather(new_cache, ("k", "v"), x.dtype,
                                             chunk_start, chunk_valid)
                k, v = g["k"], g["v"]
            else:
                k = _cache_roundtrip(k, cache["k"], x.dtype)
                v = _cache_roundtrip(v, cache["v"], x.dtype)
                kp = positions
        elif cache is not None:
            new_cache = _cache_write(cache, {"k": k, "v": v}, positions, cache_pos)
            if cache_pos is not None:
                # decode: attend over the ring buffer (upcast fp8 caches)
                k = new_cache["k"].astype(x.dtype)
                v = new_cache["v"].astype(x.dtype)
                kp = new_cache["pos"]
            else:
                # prefill from an empty cache: attend locally (flash-capable),
                # through the cache storage dtype (see _cache_roundtrip)
                k = _cache_roundtrip(k, cache["k"], x.dtype)
                v = _cache_roundtrip(v, cache["v"], x.dtype)
                kp = positions
        else:
            kp = positions
        mask = (None if y_fused is not None else
                _mask_from_pos(positions, kp, causal, window, None))

    # flash for self-attention prefill/training, and for unmasked
    # cross-attention (encoder-decoder at long frame counts)
    # chunked/bucketed prefill never flashes: bucket padding must not flip a
    # prompt across flash_min_seq into a different summation order than its
    # unpadded reference (engines route bucket >= flash_min_seq prompts to
    # the legacy per-length prefill instead)
    use_flash = (y_fused is None and cache_pos is None
                 and T >= cfg.flash_min_seq
                 and ctx.mode != "probe" and block_tables is None
                 and chunk_valid is None
                 and ((not cross and T == k.shape[1])
                      or (cross and kv_x is not None and kv_valid is None)))
    if y_fused is not None:
        y = y_fused
    elif use_flash:
        from repro.nn.flash import flash_attention
        y = flash_attention(ctx, scope, q, k, v, positions,
                            causal=causal and not cross,
                            window=window if not cross else None,
                            block=cfg.flash_block)
    else:
        y = _reference_attention(ctx, scope, q, k, v, mask)

    y = y.reshape(B, T, H * D)
    y = qops.linear(ctx, f"{scope}/o_proj", y, p["o_proj"]["w"])
    return y, new_cache


def cross_kv(p: dict, ctx: QuantContext, scope: str, cfg: AttnConfig,
             enc_out: jax.Array) -> dict:
    """Pre-compute encoder K/V for decode-time cross-attention."""
    Hkv, D = cfg.n_kv_heads, cfg.d_head
    k = _split_heads(qops.linear(ctx, f"{scope}/k_proj", enc_out,
                                 p["k_proj"]["w"], p["k_proj"].get("b")), Hkv, D)
    v = _split_heads(qops.linear(ctx, f"{scope}/v_proj", enc_out,
                                 p["v_proj"]["w"], p["v_proj"].get("b")), Hkv, D)
    return {"k": k, "v": v}


def _reference_attention(ctx, scope, q, k, v, mask):
    """Materialized-scores attention; the calibration/probe path."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    Dv = v.shape[-1]
    qg = q.reshape(B, T, Hkv, G, D)
    # L_BGEMM op #1: qk_matmul
    scores = qops.bgemm(ctx, f"{scope}/qk_matmul", "BTKGD,BSKD->BKGTS", qg, k)
    scores = scores.astype(jnp.float32) / math.sqrt(D)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask[:, None, None, :, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    # L_BGEMM op #2: av_matmul
    y = qops.bgemm(ctx, f"{scope}/av_matmul", "BKGTS,BSKD->BTKGD", probs, v)
    return y.reshape(B, T, H, Dv)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    flash_min_seq: int = 4096
    flash_block: int = 1024
    # decode-time weight absorption (DeepSeek's own serving optimization):
    # score/attend directly in the latent space instead of re-expanding
    # per-head K/V over the whole cache every step. Off by default =
    # paper-faithful baseline; enabled as a §Perf iteration.
    absorb_decode: bool = False
    # paged KV-read dequant multipliers, as in AttnConfig.kv_dequant_scales
    # (entries: "ckv", "kr"). Applied on the gather read path; the fused
    # absorbed-decode kernel rejects non-unit scales (its f32 dequant point
    # differs from the gather path's bf16 rounding, so bitwise parity is
    # impossible) — fail fast instead of silently diverging.
    kv_dequant_scales: Optional[tuple] = None


def mla_specs(prefix: str, cfg: MLAConfig) -> dict:
    dm, H = cfg.d_model, cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        f"{prefix}/q_a_proj/w": ParamSpec((r_q, dm), (None, "embed"),
                                          init="scaled_normal"),
        f"{prefix}/q_norm/scale": ParamSpec((r_q,), (None,), jnp.float32, "ones"),
        f"{prefix}/q_b_proj/w": ParamSpec((H * (dn + dr), r_q), ("heads", None),
                                          init="scaled_normal"),
        f"{prefix}/kv_a_proj/w": ParamSpec((r_kv + dr, dm), (None, "embed"),
                                           init="scaled_normal"),
        f"{prefix}/kv_norm/scale": ParamSpec((r_kv,), (None,), jnp.float32, "ones"),
        f"{prefix}/kv_b_proj/w": ParamSpec((H * (dn + dv), r_kv), ("heads", None),
                                           init="scaled_normal"),
        f"{prefix}/o_proj/w": ParamSpec((dm, H * dv), ("embed", "heads"),
                                        init="scaled_normal"),
    }


def mla_cache_spec(cfg: MLAConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    # sequence-sharded latent cache: scores/context contract tiny per-shard
    # partials (the right decode sharding for MQA-like shared-KV caches);
    # kv_lora picks up 'model' only when kv_seq can't (tiny max_len)
    return {
        "ckv": ParamSpec((batch, max_len, cfg.kv_lora_rank),
                         ("act_batch", "kv_seq", "kv_lora"), dtype, "zeros"),
        "kr": ParamSpec((batch, max_len, cfg.qk_rope_dim),
                        ("act_batch", "kv_seq", None), dtype, "zeros"),
        "pos": ParamSpec((batch, max_len), ("act_batch", "kv_seq"), jnp.int32,
                         "zeros"),
    }


def mla_page_spec(cfg: MLAConfig, n_blocks: int, block_size: int,
                  dtype=jnp.bfloat16) -> dict:
    """Paged latent KV storage (see :func:`kv_page_spec` for semantics):
    one shared latent "head", ``(n_blocks, 1, block_size, width)``."""
    return {
        "ckv": ParamSpec((n_blocks, 1, block_size, cfg.kv_lora_rank),
                         ("kv_blocks", None, None, "kv_lora"), dtype, "zeros"),
        "kr": ParamSpec((n_blocks, 1, block_size, cfg.qk_rope_dim),
                        ("kv_blocks", None, None, None), dtype, "zeros"),
    }


def mla_attention(p: dict, ctx: QuantContext, scope: str, cfg: MLAConfig,
                  x: jax.Array, positions: jax.Array, *,
                  cache: Optional[dict] = None,
                  cache_pos: Optional[jax.Array] = None,
                  block_tables: Optional[jax.Array] = None,
                  chunk_valid: Optional[jax.Array] = None,
                  chunk_start: Optional[jax.Array] = None,
                  chunk_ring: bool = False,
                  paged_attn: str = "fused"):
    """MLA; latent KV cache {"ckv","kr","pos"}; returns (y, new_cache).
    ``chunk_valid``/``chunk_start`` select chunked/bucketed prefill (see
    :func:`attention`); chunk attention always uses the expanded (non-
    absorbed) path, matching one-shot prefill. Paged *absorbed* decode takes
    the fused kernel by default (``paged_attn="fused"``), scoring/attending
    the block-major latents in place; the expanded decode path re-expands
    per-head K/V over the whole cache and therefore always gathers."""
    B, T, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    qa = qops.linear(ctx, f"{scope}/q_a_proj", x, p["q_a_proj"]["w"])
    qa = apply_norm(p["q_norm"], qa)
    q = qops.linear(ctx, f"{scope}/q_b_proj", qa, p["q_b_proj"]["w"])
    q = q.reshape(B, T, H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]

    kva = qops.linear(ctx, f"{scope}/kv_a_proj", x, p["kv_a_proj"]["w"])
    ckv, kr = kva[..., :cfg.kv_lora_rank], kva[..., cfg.kv_lora_rank:]
    ckv = apply_norm(p["kv_norm"], ckv)

    sin, cos = rope_table(positions, dr, cfg.rope_theta)
    qr = apply_rope(qr, sin, cos)
    kr = apply_rope(kr[:, :, None, :], sin, cos)[:, :, 0, :]

    new_cache = cache
    if cache is not None and block_tables is not None:
        # paged: fused absorbed decode scores the block-major latents in
        # place; chunk continuation and the expanded/fallback paths gather.
        # Non-unit dequant scales also route to the gather path: the fused
        # kernel's f32 dequant point cannot reproduce the gather path's
        # bf16 rounding, and raising mid-drain (the old fail-fast in
        # _mla_decode_absorbed_paged, kept as a backstop) would kill a
        # serving engine that merely loaded a scaled-fp8 checkpoint.
        kv_scales = dict(cfg.kv_dequant_scales or ())
        unit_scales = all(float(kv_scales.get(n, 1.0)) == 1.0
                          for n in ("ckv", "kr"))
        fused = (chunk_valid is None and cfg.absorb_decode and unit_scales
                 and use_fused_paged(ctx, scope, paged_attn)
                 and _mesh_fused_ok(B, 1))
        new_cache, g, kp = paged_update_attend(
            cache, {"ckv": ckv[:, :, None], "kr": kr[:, :, None]},
            block_tables, positions, cache_pos, chunk_valid, x.dtype,
            fused=fused, scales=kv_scales)
        if g is None:
            return _mla_decode_absorbed_paged(p, ctx, scope, cfg, qn, qr,
                                              new_cache, block_tables,
                                              positions, scales=kv_scales)
        ckv, kr = g["ckv"][:, :, 0], g["kr"][:, :, 0]
        if chunk_valid is None and cfg.absorb_decode:
            return _mla_decode_absorbed(p, ctx, scope, cfg, qn, qr, ckv,
                                        kr, positions, kp, new_cache)
    elif cache is not None and chunk_valid is not None:
        new_cache = _cache_write_chunk(cache, {"ckv": ckv, "kr": kr},
                                       positions, chunk_valid, chunk_start)
        if chunk_ring:
            # dense chunked continuation: attend the whole latent ring in
            # logical order (MLA caches are full-length, W = max_len)
            g, kp = _ring_logical_gather(new_cache, ("ckv", "kr"), x.dtype,
                                         chunk_start, chunk_valid)
            ckv, kr = g["ckv"], g["kr"]
        else:
            ckv = _cache_roundtrip(ckv, cache["ckv"], x.dtype)
            kr = _cache_roundtrip(kr, cache["kr"], x.dtype)
            kp = positions
    elif cache is not None:
        new_cache = _cache_write(cache, {"ckv": ckv, "kr": kr}, positions,
                                 cache_pos)
        if cache_pos is not None:
            ckv = new_cache["ckv"].astype(x.dtype)
            kr = new_cache["kr"].astype(x.dtype)
            kp = new_cache["pos"]
            if cfg.absorb_decode:
                return _mla_decode_absorbed(p, ctx, scope, cfg, qn, qr, ckv,
                                            kr, positions, kp, new_cache)
        else:
            # prefill from empty cache: attend locally, through the cache
            # storage dtype (see _cache_roundtrip)
            ckv = _cache_roundtrip(ckv, cache["ckv"], x.dtype)
            kr = _cache_roundtrip(kr, cache["kr"], x.dtype)
            kp = positions
    else:
        kp = positions

    # Expand latents to per-head K (nope part) and V. The expanded tensors
    # are the big ones at 32k prefill — pin their head dim to 'model'.
    from repro.distributed.sharding import shard_hint
    kvb = qops.linear(ctx, f"{scope}/kv_b_proj", ckv, p["kv_b_proj"]["w"])
    S = ckv.shape[1]
    kvb = kvb.reshape(B, S, H, dn + dv)
    kvb = shard_hint(kvb, ("pod", "data"), None, "model", None)
    kn, v = kvb[..., :dn], kvb[..., dn:]

    mask = _mask_from_pos(positions, kp, True, None, None)

    qf = jnp.concatenate([qn, qr], axis=-1)
    qf = shard_hint(qf, ("pod", "data"), None, "model", None)
    kf = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None, :], (B, S, H, dr))],
                         axis=-1)
    kf = shard_hint(kf, ("pod", "data"), None, "model", None)
    use_flash = (cache_pos is None and T >= cfg.flash_min_seq and T == S
                 and block_tables is None and chunk_valid is None)
    if use_flash:
        from repro.nn.flash import flash_attention
        y = flash_attention(ctx, scope, qf, kf, v, positions, causal=True,
                            window=None, block=cfg.flash_block)
    else:
        y = _reference_attention(ctx, scope, qf, kf, v, mask)
    y = y.reshape(B, T, H * dv)
    y = qops.linear(ctx, f"{scope}/o_proj", y, p["o_proj"]["w"])
    return y, new_cache


def _mla_decode_absorbed(p, ctx, scope, cfg: MLAConfig, qn, qr, ckv, kr,
                         positions, kp, new_cache):
    """Latent-space MLA decode: absorb W_UK into q and W_UV into the output.

    Per token: scores = (qn W_uk) . ckv + qr . kr, attention over the latent
    cache directly — O(S * (r_kv + d_rope)) per head instead of re-expanding
    (S, H, dn+dv) K/V from the latent every step.
    """
    import math as _math
    B, T, H, dn = qn.shape
    r = cfg.kv_lora_rank
    dv = cfg.v_head_dim
    # f32 operand casts: some bf16 batched-dot layouts are unimplemented on
    # the CPU backend; on TPU XLA folds the converts into the MXU op
    wkv = p["kv_b_proj"]["w"].reshape(H, dn + dv, r).astype(jnp.float32)
    w_uk, w_uv = wkv[:, :dn, :], wkv[:, dn:, :]
    # q' = qn @ W_uk  (per head) — the "absorb" GEMM
    q_lat = qops.qeinsum(ctx, f"{scope}/q_absorb", "BTHh,Hhr->BTHr",
                         qn.astype(jnp.float32), w_uk, kind="linear")
    # latent scores + rope scores (the quantizable qk_matmul analogue)
    s_lat = qops.bgemm(ctx, f"{scope}/qk_matmul", "BTHr,BSr->BHTS", q_lat, ckv)
    s_rope = jnp.einsum("BTHd,BSd->BHTS", qr.astype(jnp.float32),
                        kr.astype(jnp.float32))
    scale = 1.0 / _math.sqrt(dn + cfg.qk_rope_dim)
    s = (s_lat.astype(jnp.float32) + s_rope) * scale
    mask = _mask_from_pos(positions, kp, True, None, None)
    s = jnp.where(mask[:, None, :, :], s, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(s, axis=-1)
    # context in latent space, then expand through W_uv (av_matmul analogue)
    ctx_lat = qops.bgemm(ctx, f"{scope}/av_matmul", "BHTS,BSr->BTHr", probs,
                         ckv.astype(jnp.float32))
    y = qops.qeinsum(ctx, f"{scope}/v_absorb", "BTHr,Hvr->BTHv", ctx_lat,
                     w_uv, kind="linear")
    y = y.reshape(B, T, H * dv).astype(qn.dtype)
    y = qops.linear(ctx, f"{scope}/o_proj", y, p["o_proj"]["w"])
    return y, new_cache


def _mla_decode_absorbed_paged(p, ctx, scope, cfg: MLAConfig, qn, qr,
                               new_cache, block_tables, positions,
                               scales: Optional[dict] = None):
    """Fused-kernel twin of :func:`_mla_decode_absorbed`: the latent scores
    (``q_lat . ckv + qr . kr``) and the latent context are computed directly
    against the block-major latent cache — MQA-shaped (one shared KV "head",
    H query heads), values taken from the same ``ckv`` blocks as the keys.
    The absorb GEMMs (``q_absorb`` / ``v_absorb``) stay on ``qops`` so their
    MP formats and op names are untouched; the in-kernel math mirrors the
    reference bitwise up to f32 summation order."""
    import math as _math
    B, T, H, dn = qn.shape
    assert T == 1, "fused paged MLA is single-query decode"
    r = cfg.kv_lora_rank
    dv = cfg.v_head_dim
    wkv = p["kv_b_proj"]["w"].reshape(H, dn + dv, r).astype(jnp.float32)
    w_uk, w_uv = wkv[:, :dn, :], wkv[:, dn:, :]
    q_lat = qops.qeinsum(ctx, f"{scope}/q_absorb", "BTHh,Hhr->BTHr",
                         qn.astype(jnp.float32), w_uk, kind="linear")
    lengths = positions[:, 0] + 1
    sc = scales or {}
    if any(float(sc.get(n, 1.0)) != 1.0 for n in ("ckv", "kr")):
        # the kernel dequantizes to the f32 query dtype while the gather
        # path rounds the scaled latents through the bf16 activation dtype,
        # so non-unit scales cannot stay bit-identical between the two —
        # refuse rather than silently diverge
        raise ValueError(
            f"{scope}: fused absorbed MLA decode does not support non-unit "
            f"kv_dequant_scales (got {sc}); use paged_attn='gather'")
    ctx_lat = _paged_kernel_call(
        q_lat.reshape(B, 1, H, r),                      # (B, Hkv=1, G=H, r)
        new_cache["ckv"], None,                         # v = ckv (latent)
        block_tables, lengths,
        q2=qr.astype(jnp.float32).reshape(B, 1, H, cfg.qk_rope_dim),
        k2=new_cache["kr"],
        scale=1.0 / _math.sqrt(dn + cfg.qk_rope_dim), scale_mode="mul",
        k_scale=1.0, v_scale=1.0,  # non-unit scales rejected above
        out_dtype=jnp.float32)
    ctx_lat = ctx_lat.reshape(B, T, H, r)
    y = qops.qeinsum(ctx, f"{scope}/v_absorb", "BTHr,Hvr->BTHv", ctx_lat,
                     w_uv, kind="linear")
    y = y.reshape(B, T, H * dv).astype(qn.dtype)
    y = qops.linear(ctx, f"{scope}/o_proj", y, p["o_proj"]["w"])
    return y, new_cache
