"""Operations and bytes of the Qwen2 family's serving steps (``model_type``
"qwen2"), computed from its published sizes (the keys of a configuration
file, as in its ``config.json``): grouped-query attention over every
earlier position and a SwiGLU MLP in every layer, the output head once for
each row that yields a logit. The counting rules are ``bench/flops.py``'s.
"""
from __future__ import annotations

import math


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


def linear_flops_per_token(cfg: dict) -> float:
    """Weight-matrix FLOPs of one token through every layer (head excluded)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff
    return 2.0 * cfg["num_hidden_layers"] * per_layer


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted values of one query token over ``keys`` keys, in
    every layer."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * keys)


def prefill_row_flops(cfg: dict, start: int, valid: int) -> float:
    """One prompt chunk of ``valid`` tokens starting at position ``start``."""
    if valid <= 0:
        return 0.0
    keys = valid * start + valid * (valid + 1) / 2.0
    return (valid * linear_flops_per_token(cfg) + attention_flops(cfg, keys)
            + head_flops(cfg))


def decode_row_flops(cfg: dict, position: int) -> float:
    """One decoded token written at ``position`` (it attends position + 1
    keys)."""
    return (linear_flops_per_token(cfg) + attention_flops(cfg, position + 1)
            + head_flops(cfg))


def paged_attention_layer_cost(cfg: dict, lengths, block_size: int,
                               kv_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of the fused paged-decode kernel for one layer of
    one decode step, over rows whose live lengths are ``lengths``. Bytes
    are the live K and V pages read (whole blocks), the query and the
    output; the block table and lengths are negligible."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    flops = 0.0
    nbytes = 0.0
    for n in lengths:
        flops += 4.0 * h * dh * n
        pages = math.ceil(n / block_size)
        nbytes += 2 * pages * block_size * hkv * dh * kv_bytes
        nbytes += 2 * h * dh * 2          # bf16 query in, output out
    return flops, nbytes
