"""Decoder-only LM covering the dense / MoE / MLA / SSM / hybrid families.

One config dataclass + one model class expresses all assigned architectures
via a per-layer ``block_types`` pattern:

* ``attn``   — attention + (MLP | MoE | nothing if d_ff==0)
* ``mla``    — DeepSeek-style latent attention + (MLP | MoE)
* ``mamba``  — Mamba-2 SSD block (+ optional MLP)
* ``hybrid`` — parallel attention & mamba heads sharing the input norm (Hymba)

Two execution modes:
* unrolled (default) — every layer has its own params and op names
  (``layers/3/attn/q_proj``); required for per-layer MP and calibration.
* ``scan_layers=True`` — consecutive layers with the same signature are
  stacked into segments executed with ``jax.lax.scan`` (O(1) HLO size for the
  61-layer dry-runs). Op names are per call-site (``segments/1/attn/q_proj``);
  MP assignments then apply per segment (see DESIGN.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn import mamba as M
from repro.nn import moe as MOE
from repro.nn.spec import (ParamSpec, abstract_params, flatten_paths,
                           init_params, param_count, tree_from_flat)
from repro.quant.qops import QuantContext

BIG_WINDOW = 1 << 30  # "no window" sentinel for traced window values


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    vocab_size: int
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None
    global_attn_layers: tuple = ()        # layers exempt from the window
    # MLA (block type "mla")
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    mla_absorb_decode: bool = False       # latent-space decode (§Perf lever)
    # mlp
    d_ff: int = 0
    activation: str = "swiglu"
    norm: str = "rmsnorm"
    # blocks
    block_types: tuple = ()               # len == n_layers
    moe_layers: tuple = ()                # layer idxs with MoE instead of MLP
    moe: Optional[MOE.MoEConfig] = None
    ssm: Optional[M.SSMConfig] = None
    # head
    tie_embeddings: bool = False
    # multimodal stub (llava / audio): accepts prefix embeddings
    prefix_embed: bool = False
    # MTP (DeepSeek-V3 multi-token prediction) — adds one extra block
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # infra
    scan_layers: bool = False
    remat: bool = False
    remat_group: int = 8                  # two-level remat group (train scans)
    loss_chunk: int = 1024                # seq chunk for the CE loss
    flash_min_seq: int = 4096
    flash_block: int = 1024
    dtype: str = "bfloat16"
    kv_cache_dtype: str = "bfloat16"      # fp8_e4m3 halves decode cache HBM
    # paged KV-cache dequant multipliers (scaled fp8 KV): None | a tuple of
    # (entry, scale) pairs applied to every layer | a per-layer tuple (len
    # n_layers) of such pair-tuples (None entries = unit scales). Entries:
    # "k"/"v" (attention blocks) or "ckv"/"kr" (MLA). Writes divide by the
    # scale before the fp8 cast, reads multiply it back — see
    # repro.quant.kv_scales.calibrate_kv_scales for producing these from a
    # calibration pass. Paged serving only; dense rings ignore scales.
    kv_dequant_scales: Optional[tuple] = None
    # store matmul weights in fp8 (the paper's IP-M objective realized):
    # halves weight HBM + FSDP gather bytes; dequant folds into the GEMM
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.block_types:
            object.__setattr__(self, "block_types", ("attn",) * self.n_layers)
        assert len(self.block_types) == self.n_layers
        sc = self.kv_dequant_scales
        if sc is not None:
            sc = tuple(sc)
            if self._scales_are_per_layer(sc):
                sc = tuple(None if e is None else
                           tuple((str(n), float(s)) for n, s in e)
                           for e in sc)
                if len(sc) != self.n_layers:
                    raise ValueError(
                        f"per-layer kv_dequant_scales has {len(sc)} entries "
                        f"for {self.n_layers} layers")
                if self.scan_layers and len(set(sc)) > 1:
                    raise ValueError(
                        "scan_layers stacks layers into shared-trace "
                        "segments, so per-layer kv_dequant_scales must be "
                        "uniform — pass one global pair-tuple instead")
            else:
                sc = tuple((str(n), float(s)) for n, s in sc)
            object.__setattr__(self, "kv_dequant_scales", sc)

    @staticmethod
    def _scales_are_per_layer(sc: tuple) -> bool:
        """Global form: ((name, scale), ...); per-layer form: one entry per
        layer, each None or a pair-tuple."""
        first = next((e for e in sc if e is not None), None)
        if first is None:
            return True
        return not (len(first) == 2 and isinstance(first[0], str))

    def kv_scales_for(self, i: Optional[int]) -> Optional[tuple]:
        """Dequant-scale pairs for layer ``i`` (None = unit scales).
        ``i=None`` (scan segments, MTP block) returns the global pairs, or
        None under a per-layer table — per-layer + scan is rejected at
        construction unless uniform."""
        sc = self.kv_dequant_scales
        if sc is None:
            return None
        if self._scales_are_per_layer(sc):
            if i is None:
                return sc[0] if self.scan_layers and sc else None
            return sc[i]
        return sc

    # ---- derived ----
    @property
    def attn_cfg(self) -> L.AttnConfig:
        return self.attn_cfg_for(None)

    def attn_cfg_for(self, i: Optional[int]) -> L.AttnConfig:
        return L.AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                            self.d_head, qkv_bias=self.qkv_bias,
                            rope_theta=self.rope_theta,
                            window=self.sliding_window,
                            flash_min_seq=self.flash_min_seq,
                            flash_block=self.flash_block,
                            kv_dequant_scales=self.kv_scales_for(i))

    @property
    def mla_cfg(self) -> L.MLAConfig:
        return self.mla_cfg_for(None)

    def mla_cfg_for(self, i: Optional[int]) -> L.MLAConfig:
        return L.MLAConfig(self.d_model, self.n_heads, self.q_lora_rank,
                           self.kv_lora_rank, self.qk_nope_dim,
                           self.qk_rope_dim, self.v_head_dim, self.rope_theta,
                           flash_min_seq=self.flash_min_seq,
                           flash_block=self.flash_block,
                           absorb_decode=self.mla_absorb_decode,
                           kv_dequant_scales=self.kv_scales_for(i))

    def layer_signature(self, i: int) -> tuple:
        return (self.block_types[i], i in self.moe_layers)

    def window_for(self, i: int) -> Optional[int]:
        if self.sliding_window is None or i in self.global_attn_layers:
            return None
        return self.sliding_window

    def segments(self) -> list:
        """Consecutive layers grouped by signature: [(sig, [idx...]), ...]."""
        segs: list = []
        for i in range(self.n_layers):
            sig = self.layer_signature(i)
            if segs and segs[-1][0] == sig:
                segs[-1][1].append(i)
            else:
                segs.append((sig, [i]))
        return segs


class LM:
    # serving capability flags (engines dispatch on these, not on isinstance)
    cache_needs_enc_len = False
    supports_prefill_chunk = True        # bucketed/chunked prefill available

    def __init__(self, cfg: LMConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------
    # specs
    # ------------------------------------------------------------------
    def _layer_specs(self, sig: tuple, prefix: str) -> dict:
        cfg = self.cfg
        block, is_moe = sig
        specs: dict = {}
        specs.update(L.norm_specs(f"{prefix}/attn_norm", cfg.d_model, cfg.norm))
        if block == "attn":
            specs.update(L.attn_specs(f"{prefix}/attn", cfg.attn_cfg))
        elif block == "mla":
            specs.update(L.mla_specs(f"{prefix}/attn", cfg.mla_cfg))
        elif block == "mamba":
            specs.update(M.mamba_specs(f"{prefix}/mamba", cfg.ssm))
        elif block == "hybrid":
            specs.update(L.attn_specs(f"{prefix}/attn", cfg.attn_cfg))
            specs.update(M.mamba_specs(f"{prefix}/mamba", cfg.ssm))
        else:
            raise ValueError(block)
        if is_moe:
            specs.update(L.norm_specs(f"{prefix}/mlp_norm", cfg.d_model, cfg.norm))
            specs.update(MOE.moe_specs(f"{prefix}/moe", cfg.d_model, cfg.moe,
                                       cfg.activation))
        elif cfg.d_ff > 0:
            specs.update(L.norm_specs(f"{prefix}/mlp_norm", cfg.d_model, cfg.norm))
            specs.update(L.mlp_specs(f"{prefix}/mlp", cfg.d_model, cfg.d_ff,
                                     cfg.activation))
        return specs

    def _apply_param_dtype(self, specs: dict) -> dict:
        """Store >=2D matmul weights in cfg.param_dtype (fp8 serving)."""
        cfg = self.cfg
        if cfg.param_dtype == "bfloat16":
            return specs
        from repro.quant.formats import get_format
        dt = get_format(cfg.param_dtype).dtype
        out = {}
        for path, ps in specs.items():
            quantizable = (path.endswith("/w") and len(ps.shape) >= 2
                           and not path.startswith("embed"))
            out[path] = (ParamSpec(ps.shape, ps.logical_axes, dt, ps.init,
                                   ps.init_scale) if quantizable else ps)
        return out

    def param_specs(self) -> dict:
        cfg = self.cfg
        specs: dict = {
            "embed/w": ParamSpec((cfg.vocab_size, cfg.d_model),
                                 ("vocab", "embed"), init="normal"),
        }
        specs.update(L.norm_specs("final_norm", cfg.d_model, cfg.norm))
        if not cfg.tie_embeddings:
            specs["lm_head/w"] = ParamSpec((cfg.vocab_size, cfg.d_model),
                                           ("vocab", "embed"),
                                           init="scaled_normal")
        if cfg.scan_layers:
            for s, (sig, idxs) in enumerate(cfg.segments()):
                layer = self._layer_specs(sig, f"segments/{s}")
                for path, ps in layer.items():
                    specs[path] = ParamSpec((len(idxs),) + ps.shape,
                                            ("layers",) + ps.logical_axes,
                                            ps.dtype, ps.init, ps.init_scale)
        else:
            for i in range(cfg.n_layers):
                specs.update(self._layer_specs(cfg.layer_signature(i),
                                               f"layers/{i}"))
        if cfg.mtp_depth > 0:
            specs["mtp/proj/w"] = ParamSpec((cfg.d_model, 2 * cfg.d_model),
                                            ("embed", None), init="scaled_normal")
            specs.update(L.norm_specs("mtp/norm", cfg.d_model, cfg.norm))
            specs.update(self._layer_specs(self.cfg.layer_signature(
                cfg.n_layers - 1), "mtp/block"))
        return self._apply_param_dtype(specs)

    def init(self, key: jax.Array) -> dict:
        return init_params(key, self.param_specs())

    def n_params(self) -> int:
        return param_count(self.param_specs())

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _block(self, p: dict, ctx: QuantContext, scope: str, sig: tuple,
               h: jax.Array, positions: jax.Array, *,
               window="cfg", cache: Optional[dict] = None,
               cache_pos=None, decode: bool = False,
               block_tables: Optional[jax.Array] = None,
               chunk_valid: Optional[jax.Array] = None,
               chunk_start: Optional[jax.Array] = None,
               chunk_ring: bool = False,
               layer_idx: Optional[int] = None,
               paged_attn: str = "fused"):
        cfg = self.cfg
        block, is_moe = sig
        new_cache = cache
        hn = L.apply_norm(p["attn_norm"], h, cfg.norm)
        aux = jnp.zeros((), jnp.float32)
        resume = None if chunk_start is None else chunk_start > 0
        # paged decode: a block-table row of -1 marks a vacant or mid-prefill
        # slot — its SSM state must pass through the step untouched, exactly
        # like its K/V writes go to the trash block
        row_valid = (block_tables[:, 0] >= 0
                     if decode and block_tables is not None else None)
        if block == "attn":
            y, new_cache = L.attention(p["attn"], ctx, f"{scope}/attn",
                                       cfg.attn_cfg_for(layer_idx), hn,
                                       positions,
                                       cache=cache, cache_pos=cache_pos,
                                       block_tables=block_tables,
                                       chunk_valid=chunk_valid,
                                       chunk_start=chunk_start,
                                       chunk_ring=chunk_ring,
                                       window=window, paged_attn=paged_attn)
        elif block == "mla":
            y, new_cache = L.mla_attention(p["attn"], ctx, f"{scope}/attn",
                                           cfg.mla_cfg_for(layer_idx), hn,
                                           positions,
                                           cache=cache, cache_pos=cache_pos,
                                           block_tables=block_tables,
                                           chunk_valid=chunk_valid,
                                           chunk_start=chunk_start,
                                           chunk_ring=chunk_ring,
                                           paged_attn=paged_attn)
        elif block == "mamba":
            if decode:
                y, new_cache = M.apply_mamba_decode(p["mamba"], ctx,
                                                    f"{scope}/mamba", cfg.ssm,
                                                    hn, cache,
                                                    row_valid=row_valid)
            else:
                y, new_cache = M.apply_mamba(p["mamba"], ctx, f"{scope}/mamba",
                                             cfg.ssm, hn, cache,
                                             chunk_valid=chunk_valid,
                                             resume=resume)
        elif block == "hybrid":
            a_cache = None if cache is None else cache.get("attn")
            m_cache = None if cache is None else cache.get("mamba")
            ya, a_new = L.attention(p["attn"], ctx, f"{scope}/attn",
                                    cfg.attn_cfg_for(layer_idx), hn,
                                    positions,
                                    cache=a_cache, cache_pos=cache_pos,
                                    block_tables=block_tables,
                                    chunk_valid=chunk_valid,
                                    chunk_start=chunk_start,
                                    chunk_ring=chunk_ring, window=window,
                                    paged_attn=paged_attn)
            if decode:
                ym, m_new = M.apply_mamba_decode(p["mamba"], ctx,
                                                 f"{scope}/mamba", cfg.ssm,
                                                 hn, m_cache,
                                                 row_valid=row_valid)
            else:
                ym, m_new = M.apply_mamba(p["mamba"], ctx, f"{scope}/mamba",
                                          cfg.ssm, hn, m_cache,
                                          chunk_valid=chunk_valid,
                                          resume=resume)
            y = 0.5 * (ya + ym)
            if cache is not None:
                new_cache = {"attn": a_new, "mamba": m_new}
        else:
            raise ValueError(block)
        h = h + y
        if is_moe:
            hn2 = L.apply_norm(p["mlp_norm"], h, cfg.norm)
            ym, aux = MOE.apply_moe(p["moe"], ctx, f"{scope}/moe", hn2,
                                    cfg.moe, cfg.activation)
            h = h + ym
        elif cfg.d_ff > 0:
            hn2 = L.apply_norm(p["mlp_norm"], h, cfg.norm)
            h = h + L.apply_mlp(p["mlp"], ctx, f"{scope}/mlp", hn2,
                                cfg.activation)
        return h, new_cache, aux

    def _backbone(self, params: dict, ctx: QuantContext, h: jax.Array,
                  positions: jax.Array, *, caches: Optional[dict] = None,
                  cache_pos=None, decode: bool = False,
                  block_tables: Optional[jax.Array] = None,
                  chunk_valid: Optional[jax.Array] = None,
                  chunk_start: Optional[jax.Array] = None,
                  chunk_ring: bool = False,
                  paged_attn: str = "fused"):
        """Run all layers. caches: {"layers/i" or "segments/s": cache pytree}."""
        from repro.distributed.sharding import shard_hint
        cfg = self.cfg
        # pin the residual stream to batch-sharding: without this, FSDP
        # weight shardings propagate into h (batch replicated, d_model
        # sharded) and the layer-scan residual stack inflates 16x
        h = shard_hint(h, ("pod", "data"), None, None)
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = {} if caches is not None else None
        if cfg.scan_layers:
            for s, (sig, idxs) in enumerate(cfg.segments()):
                seg_params = params["segments"][str(s)]
                windows = jnp.array(
                    [w if (w := cfg.window_for(i)) is not None else BIG_WINDOW
                     for i in idxs], jnp.int32)
                seg_cache = None if caches is None else caches[f"segments/{s}"]

                def body(carry, xs):
                    h_, aux_ = carry
                    p_i, win_i, cache_i = xs
                    h_, c_new, aux_i = self._block(
                        p_i, ctx, f"segments/{s}", sig, h_, positions,
                        window=win_i, cache=cache_i, cache_pos=cache_pos,
                        decode=decode, block_tables=block_tables,
                        chunk_valid=chunk_valid, chunk_start=chunk_start,
                        chunk_ring=chunk_ring, paged_attn=paged_attn)
                    return (h_, aux_ + aux_i), c_new

                if cfg.remat:
                    body = jax.checkpoint(body)
                # NOTE: no sharding constraint inside the scan body — a wsc
                # in a scanned-over region makes partial-eval stack an f32
                # copy of the carry per layer (21GB at 32B scale). The entry
                # constraint + input batch constraints keep propagation sane.
                xs = (seg_params, windows, seg_cache)
                G = cfg.remat_group
                n_seg = len(idxs)
                main = (n_seg // G) * G if G > 1 else 0
                if cfg.remat and caches is None and main >= 2 * G:
                    # two-level remat scan: residual stacks shrink from O(L)
                    # to O(L/G + G) h-sized entries (sqrt-remat); a remainder
                    # of n_seg % G layers runs as a plain scan tail
                    xs_main = jax.tree.map(lambda a: a[:main], xs)
                    xs_tail = jax.tree.map(lambda a: a[main:], xs)
                    xs_g = jax.tree.map(
                        lambda a: a.reshape(main // G, G, *a.shape[1:]),
                        xs_main)

                    def group_body(carry, xs_i):
                        return jax.lax.scan(body, carry, xs_i)

                    (h, aux_total), seg_cache_new = jax.lax.scan(
                        jax.checkpoint(group_body), (h, aux_total), xs_g)
                    if main < n_seg:
                        (h, aux_total), _tail_cache = jax.lax.scan(
                            body, (h, aux_total), xs_tail)
                else:
                    (h, aux_total), seg_cache_new = jax.lax.scan(
                        body, (h, aux_total), xs)
                if new_caches is not None:
                    new_caches[f"segments/{s}"] = seg_cache_new
        else:
            for i in range(cfg.n_layers):
                sig = cfg.layer_signature(i)
                cache_i = None if caches is None else caches[f"layers/{i}"]

                def body(p_i, h_, cache_i_):
                    return self._block(p_i, ctx, f"layers/{i}", sig, h_,
                                       positions, window=cfg.window_for(i),
                                       cache=cache_i_, cache_pos=cache_pos,
                                       decode=decode,
                                       block_tables=block_tables,
                                       chunk_valid=chunk_valid,
                                       chunk_start=chunk_start,
                                       chunk_ring=chunk_ring, layer_idx=i,
                                       paged_attn=paged_attn)

                if cfg.remat:
                    body = jax.checkpoint(body)
                h, c_new, aux_i = body(params["layers"][str(i)], h, cache_i)
                aux_total = aux_total + aux_i
                if new_caches is not None:
                    new_caches[f"layers/{i}"] = c_new
        h = L.apply_norm(params["final_norm"], h, cfg.norm)
        return h, new_caches, aux_total

    def _embed(self, params: dict, tokens: jax.Array,
               prefix_embeds: Optional[jax.Array]) -> tuple:
        emb = jnp.take(params["embed"]["w"], tokens, axis=0).astype(self.dtype)
        if prefix_embeds is not None:
            emb = jnp.concatenate([prefix_embeds.astype(self.dtype), emb], axis=1)
        B, T = emb.shape[0], emb.shape[1]
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                     (B, T))
        return emb, positions

    def _head(self, params: dict, ctx: QuantContext, h: jax.Array) -> jax.Array:
        w = params["embed"]["w"] if self.cfg.tie_embeddings else params["lm_head"]["w"]
        from repro.quant import qops
        return qops.linear(ctx, "lm_head", h, w)

    def apply(self, params: dict, tokens: jax.Array, ctx: QuantContext, *,
              prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
        """Full forward -> logits (B, T, V). For small models/tests."""
        h, positions = self._embed(params, tokens, prefix_embeds)
        h, _, _ = self._backbone(params, ctx, h, positions)
        return self._head(params, ctx, h)

    # ------------------------------------------------------------------
    # loss (chunked over sequence so (T, vocab) logits never materialize)
    # ------------------------------------------------------------------
    def loss(self, params: dict, batch: dict, ctx: QuantContext) -> jax.Array:
        cfg = self.cfg
        tokens = batch["tokens"]
        labels = batch["labels"]
        weights = batch.get("weights")
        h, positions = self._embed(params, tokens, batch.get("prefix_embeds"))
        h, _, aux = self._backbone(params, ctx, h, positions)
        if batch.get("prefix_embeds") is not None:
            h = h[:, -tokens.shape[1]:]  # loss only over text positions
        from repro.nn.losses import chunked_ce_loss
        loss = chunked_ce_loss(lambda hi: self._head(params, ctx, hi), h,
                               labels, weights, cfg.loss_chunk,
                               no_scan=(ctx.mode == "probe"))
        if cfg.mtp_depth > 0:
            B, T, _ = h.shape
            if weights is None:
                weights = jnp.ones((B, T), jnp.float32)
            mtp_fn = self._mtp_loss
            if cfg.remat:
                mtp_fn = jax.checkpoint(mtp_fn, static_argnums=(1,))
            loss = loss + cfg.mtp_weight * mtp_fn(
                params, ctx, h, tokens, labels, weights)
        return loss + aux

    def _mtp_loss(self, params, ctx, h, tokens, labels, weights):
        """DeepSeek-V3 multi-token prediction: predict t+2 from (h_t, emb_{t+1})."""
        cfg = self.cfg
        emb_next = jnp.take(params["embed"]["w"], labels, axis=0).astype(self.dtype)
        hcat = jnp.concatenate([h, emb_next], axis=-1)
        from repro.quant import qops
        hm = qops.linear(ctx, "mtp/proj", hcat, params["mtp"]["proj"]["w"])
        hm = L.apply_norm(params["mtp"]["norm"], hm, cfg.norm)
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        hm, _, _ = self._block(params["mtp"]["block"], ctx, "mtp/block",
                               cfg.layer_signature(cfg.n_layers - 1), hm,
                               positions)
        # targets: labels shifted by one more step
        tgt = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)))
        w = jnp.pad(weights[:, 1:], ((0, 0), (0, 1)))
        from repro.nn.losses import chunked_ce_loss
        return chunked_ce_loss(lambda hi: self._head(params, ctx, hi), hm,
                               tgt, w, cfg.loss_chunk,
                               no_scan=(ctx.mode == "probe"))

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    @property
    def _kv_dtype(self):
        return (jnp.float8_e4m3fn if self.cfg.kv_cache_dtype == "fp8_e4m3"
                else self.dtype)

    def _assemble_cache_specs(self, one) -> dict:
        """Stitch per-layer cache specs (``one(sig) -> {sub: tree}``) into the
        flat ``layers/i@sub/path`` (or ``segments/s@...``) namespace."""
        cfg = self.cfg
        specs: dict = {}
        if cfg.scan_layers:
            for s, (sig, idxs) in enumerate(cfg.segments()):
                for sub, tree in one(sig).items():
                    for path, ps in flatten_paths(tree).items():
                        specs[f"segments/{s}@{sub}/{path}"] = ParamSpec(
                            (len(idxs),) + ps.shape,
                            ("layers",) + ps.logical_axes, ps.dtype, "zeros")
        else:
            for i in range(cfg.n_layers):
                for sub, tree in one(cfg.layer_signature(i)).items():
                    for path, ps in flatten_paths(tree).items():
                        specs[f"layers/{i}@{sub}/{path}"] = ps
        return specs

    def cache_specs(self, batch: int, max_len: int,
                    ring_window: bool = True, chunk_extra: int = 0) -> dict:
        """Flat path->ParamSpec dict for the dense KV/SSM caches.
        ``ring_window=False`` keeps full ``max_len`` K/V rows for
        sliding-window layers (window enforced by mask only) — required for
        a prefill cache that will be reshaped into paged blocks.
        ``chunk_extra`` widens windowed rings to ``window + chunk_extra``
        rows so dense chunked prefill never evicts in-window keys (engines
        pass their ``chunk_len``; see :func:`repro.nn.layers.kv_cache_spec`)."""
        cfg = self.cfg
        kv_dtype = self._kv_dtype

        def one(sig) -> dict:
            block, _ = sig
            if block == "attn":
                return {"attn": L.kv_cache_spec(cfg.attn_cfg, batch, max_len,
                                                kv_dtype, ring=ring_window,
                                                chunk_extra=chunk_extra)}
            if block == "mla":
                return {"attn": L.mla_cache_spec(cfg.mla_cfg, batch, max_len,
                                                 kv_dtype)}
            if block == "mamba":
                return {"mamba": M.mamba_cache_spec(cfg.ssm, batch, self.dtype)}
            if block == "hybrid":
                return {"attn": L.kv_cache_spec(cfg.attn_cfg, batch, max_len,
                                                kv_dtype, ring=ring_window,
                                                chunk_extra=chunk_extra),
                        "mamba": M.mamba_cache_spec(cfg.ssm, batch, self.dtype)}
            raise ValueError(block)

        return self._assemble_cache_specs(one)

    def paged_cache_specs(self, n_slots: int, n_blocks: int,
                          block_size: int) -> dict:
        """Flat specs for paged serving: attention K/V (and MLA latents) are
        head-major ``(n_blocks, heads, block_size, width)`` shared storage;
        SSM state has no sequence axis and stays slot-major
        ``(n_slots, ...)``."""
        cfg = self.cfg
        kv_dtype = self._kv_dtype

        def one(sig) -> dict:
            block, _ = sig
            if block == "attn":
                return {"attn": L.kv_page_spec(cfg.attn_cfg, n_blocks,
                                               block_size, kv_dtype)}
            if block == "mla":
                return {"attn": L.mla_page_spec(cfg.mla_cfg, n_blocks,
                                                block_size, kv_dtype)}
            if block == "mamba":
                return {"mamba": M.mamba_cache_spec(cfg.ssm, n_slots,
                                                    self.dtype)}
            if block == "hybrid":
                return {"attn": L.kv_page_spec(cfg.attn_cfg, n_blocks,
                                               block_size, kv_dtype),
                        "mamba": M.mamba_cache_spec(cfg.ssm, n_slots,
                                                    self.dtype)}
            raise ValueError(block)

        return self._assemble_cache_specs(one)

    @staticmethod
    def _cache_tree(flat_specs_or_vals: dict) -> dict:
        """'layers/0@attn/k' flat keys -> {"layers/0": {"attn": {"k": ...}}}."""
        out: dict = {}
        for key, v in flat_specs_or_vals.items():
            head, rest = key.split("@", 1)
            sub = rest.split("/")
            node = out.setdefault(head, {})
            for spart in sub[:-1]:
                node = node.setdefault(spart, {})
            node[sub[-1]] = v
        return out

    def init_cache(self, batch: int, max_len: int, abstract: bool = False,
                   ring_window: bool = True, chunk_extra: int = 0) -> dict:
        return self._materialize_cache(
            self.cache_specs(batch, max_len, ring_window=ring_window,
                             chunk_extra=chunk_extra),
            abstract)

    def init_paged_cache(self, n_slots: int, n_blocks: int, block_size: int,
                         abstract: bool = False) -> dict:
        return self._materialize_cache(
            self.paged_cache_specs(n_slots, n_blocks, block_size), abstract)

    @classmethod
    def assemble_cache_tree(cls, flat: dict) -> dict:
        """Flat ``layers/i@sub/path`` keys -> the nested cache pytree the
        engines carry (same structure for any leaf values — specs, arrays,
        or shardings, so a sharding tree built from cache *specs* always
        ``tree.map``s against the materialized cache)."""
        tree = cls._cache_tree(flat)
        # unwrap single-sub caches: {"attn": {...}} -> cache dict for _block
        out = {}
        for lk, subs in tree.items():
            if set(subs) == {"attn"}:
                out[lk] = subs["attn"]
            elif set(subs) == {"mamba"}:
                out[lk] = subs["mamba"]
            else:
                out[lk] = subs
        return out

    def _materialize_cache(self, specs: dict, abstract: bool = False) -> dict:
        if abstract:
            flat = {k: jax.ShapeDtypeStruct(s.shape, s.dtype)
                    for k, s in specs.items()}
        else:
            flat = {}
            for k, s in specs.items():
                if k.endswith("/pos"):
                    flat[k] = jnp.full(s.shape, -1, jnp.int32)
                else:
                    flat[k] = jnp.zeros(s.shape, s.dtype)
        return self.assemble_cache_tree(flat)

    def paged_insert(self, paged: dict, dense1: dict, block_ids: jax.Array,
                     slot: jax.Array) -> dict:
        """Scatter a freshly prefilled batch=1 dense cache into paged storage.

        Page-major leaves (attention K/V, MLA latents) land in the physical
        blocks named by ``block_ids``, head-major; the dense prefill length
        must equal ``len(block_ids) * block_size`` so the reshape is exact.
        Slot-major leaves (SSM state) overwrite row ``slot``. The dense
        ``pos`` ring is dropped: paged attention derives key positions from
        block-table order. Pure function of its array args — jit it once
        per distinct prompt-block count.
        """
        scan = self.cfg.scan_layers
        nb = block_ids.shape[0]
        slot = jnp.asarray(slot, jnp.int32)

        def rec(pv, dv):
            if isinstance(pv, dict):
                if "pos" in dv and "pos" not in pv:    # attention page node
                    out = {}
                    for name, leaf in pv.items():
                        # dense (S, [heads,] width) -> (nb, heads, bs, width)
                        src = dv[name][:, 0] if scan else dv[name][0]
                        lead = src.shape[:1] if scan else ()
                        h, bs, w = leaf.shape[-3:]
                        s = src.reshape(lead + (nb, bs, h, w)).swapaxes(-3, -2)
                        out[name] = (leaf.at[:, block_ids] if scan
                                     else leaf.at[block_ids]).set(
                                         s.astype(leaf.dtype))
                    return out
                return {k: rec(v, dv[k]) for k, v in pv.items()}
            # slot-major leaf (SSM state): overwrite row ``slot``
            axis = 1 if scan else 0
            start = (0,) * axis + (slot,) + (0,) * (pv.ndim - axis - 1)
            return jax.lax.dynamic_update_slice(pv, dv.astype(pv.dtype), start)

        return {k: rec(v, dense1[k]) for k, v in paged.items()}

    def paged_copy_block(self, paged: dict, src: jax.Array,
                         dst: jax.Array) -> dict:
        """Copy physical block ``src`` into block ``dst`` on every page-major
        cache leaf (attention K/V, MLA latents); slot-major leaves (SSM
        state) pass through untouched.

        This is the pool's copy-on-write fork: a slot about to write into a
        block it shares with other slots first duplicates the block and
        repoints its table entry at the copy, so the parent chain other
        requests attend is never mutated. Pure function of its array args —
        the pool jits it once per (model, layout) and traces over the
        src/dst block ids."""
        axes = self.assemble_cache_tree({
            k: (s.logical_axes.index("kv_blocks")
                if "kv_blocks" in s.logical_axes else -1)
            for k, s in self.paged_cache_specs(1, 1, 1).items()})
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)

        def cp(leaf, ax):
            if ax < 0:
                return leaf
            blk = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax)
            return jax.lax.dynamic_update_slice_in_dim(leaf, blk, dst, ax)

        return jax.tree.map(cp, paged, axes)

    def prefill(self, params: dict, tokens: jax.Array, caches: dict,
                ctx: QuantContext, *,
                prefix_embeds: Optional[jax.Array] = None):
        """Process the prompt; returns (last-token logits, caches)."""
        h, positions = self._embed(params, tokens, prefix_embeds)
        h, caches, _ = self._backbone(params, ctx, h, positions, caches=caches)
        logits = self._head(params, ctx, h[:, -1:])
        return logits, caches

    def prefill_chunk(self, params: dict, tokens: jax.Array, caches: dict,
                      ctx: QuantContext, *, start_pos: jax.Array,
                      valid_len: jax.Array,
                      block_tables: Optional[jax.Array] = None,
                      chunk_ring: bool = False):
        """Process one (possibly padded) prompt chunk for every cache row.

        The batched/bucketed twin of :meth:`prefill`: every row of
        ``tokens`` (B, Lb) is padded to a shared bucket length, so engines
        compile one program per bucket instead of one per distinct prompt
        length, and B matches the decode batch so the step is shape-stable.

        * ``start_pos`` (B,): absolute position of ``tokens[:, 0]``. 0 marks
          a first chunk — it resets the row's ring ``pos`` entries (dense)
          and SSM state, so slot reuse cannot leak the previous occupant.
          Engines pass a nonzero start for vacant/decoding rows.
        * ``valid_len`` (B,): real token count per row; 0 = inactive row
          (its caches/state pass through bit-unchanged, its writes go to the
          trash block / are dropped).
        * ``block_tables`` (B, max_blocks): paged mode — the chunk's K/V is
          written straight into physical blocks ("paged prefill") and
          attention runs over the gathered logical layout, so prompts longer
          than a chunk resume exactly where the previous chunk stopped.
          None = dense bucketed single-shot prefill into the row's ring.
        * ``chunk_ring``: dense continuation mode — attend the whole ring
          gathered into logical order instead of only the chunk's local K/V,
          so dense engines can split prompts into chunks too. Windowed archs
          need rings widened by ``chunk_len`` (``init_cache(chunk_extra=)``).

        Returns (logits (B, 1, V) at each row's last valid position, caches).
        """
        B, T = tokens.shape
        start = jnp.asarray(start_pos, jnp.int32)
        valid = jnp.asarray(valid_len, jnp.int32)
        emb = jnp.take(params["embed"]["w"], tokens, axis=0).astype(self.dtype)
        positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        chunk_valid = jnp.arange(T, dtype=jnp.int32)[None] < valid[:, None]
        h, caches, _ = self._backbone(params, ctx, emb, positions,
                                      caches=caches, chunk_valid=chunk_valid,
                                      chunk_start=start,
                                      chunk_ring=chunk_ring,
                                      block_tables=block_tables)
        idx = jnp.maximum(valid - 1, 0)          # inactive rows: garbage out
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
        logits = self._head(params, ctx, h_last)
        return logits, caches

    def decode_step(self, params: dict, token: jax.Array, pos: jax.Array,
                    caches: dict, ctx: QuantContext, *,
                    block_tables: Optional[jax.Array] = None,
                    paged_attn: str = "fused"):
        """One token for every sequence. token: (B,1); pos: scalar int32 for
        a lock-step batch, or (B,) int32 with one position per sequence
        (continuous batching: every cache slot decodes at its own depth).
        ``block_tables`` (B, max_blocks) switches attention caches to the
        paged layout (shared across layers; SSM state stays slot-major);
        each row's per-row length is its position + 1, which the default
        fused paged-attention kernel masks against — ``paged_attn="gather"``
        selects the reference gather-then-attend path instead."""
        emb = jnp.take(params["embed"]["w"], token, axis=0).astype(self.dtype)
        B = token.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 1:
            positions = pos[:, None]
        else:
            positions = jnp.broadcast_to(pos[None, None], (B, 1))
        h, caches, _ = self._backbone(params, ctx, emb, positions,
                                      caches=caches, cache_pos=pos,
                                      decode=True, block_tables=block_tables,
                                      paged_attn=paged_attn)
        logits = self._head(params, ctx, h)
        return logits, caches

    # ------------------------------------------------------------------
    # abstract views
    # ------------------------------------------------------------------
    def abstract_params(self, shardings: Optional[dict] = None) -> dict:
        return abstract_params(self.param_specs(), shardings)
