"""The program's model for a published Qwen2 ``config.json``
(``model_type`` "qwen2": Qwen2 and Qwen2.5): pre-norm RMSNorm blocks,
grouped-query attention with biased q/k/v projections and rotary
embeddings, a SwiGLU MLP. Keys it cannot serve are refused by name."""
from __future__ import annotations

import dataclasses

# the RMSNorm epsilon of a program whose LMConfig has no ``norm_eps`` field
PROGRAM_NORM_EPS = 1e-6


def lm_config(name: str, cfg: dict):
    from repro.models.lm import LMConfig
    if cfg.get("use_sliding_window"):
        raise ValueError("use_sliding_window: sliding-window Qwen2 layers "
                         "are not served")
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: only silu "
                         f"(a SwiGLU MLP) is served")
    eps = float(cfg["rms_norm_eps"])
    extra = {}
    if "norm_eps" in {f.name for f in dataclasses.fields(LMConfig)}:
        extra["norm_eps"] = eps
    elif eps != PROGRAM_NORM_EPS:
        raise ValueError(f"rms_norm_eps {eps:g}: the program's RMSNorm "
                         f"epsilon is fixed at {PROGRAM_NORM_EPS:g}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return LMConfig(name=name, n_layers=cfg["num_hidden_layers"],
                    d_model=d, vocab_size=cfg["vocab_size"], n_heads=h,
                    n_kv_heads=cfg["num_key_value_heads"],
                    d_head=cfg.get("head_dim") or d // h,
                    d_ff=cfg["intermediate_size"], qkv_bias=True,
                    activation="swiglu", norm="rmsnorm",
                    rope_theta=float(cfg["rope_theta"]),
                    tie_embeddings=bool(cfg["tie_word_embeddings"]),
                    **extra)
