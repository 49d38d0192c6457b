"""Plain float32 reference of the Qwen2 decoder (Qwen2.5 family), written
from the published description: pre-norm RMSNorm blocks, grouped-query
attention with biased q/k/v projections and rotary embeddings (rotate-half
form, frequencies ``theta ** (-2i / head_dim)``), a SwiGLU MLP, a final
RMSNorm and the output head tied to the embedding.

It imports nothing of the system under test. Its weights are the
benchmark's own (``bench/weights.py``), laid out as ``param_layout`` says;
matrices are stored ``(out, in)`` and applied as ``x @ w.T``. Every matrix
product runs at ``Precision.HIGHEST``, so a TPU does not round it to bf16.
The forward pass runs layer by layer, one compiled layer program for all
layers, so it fits beside the served model's weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _dims(cfg: dict) -> tuple:
    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, hkv, dh, cfg["intermediate_size"]


def param_layout(cfg: dict) -> dict:
    """path -> (shape, dtype, init kind) of every weight. Init kinds:
    ``embed``, ``matrix`` (fan-in scaled), ``bias`` and ``norm``."""
    d, h, hkv, dh, ff = _dims(cfg)
    bf, f32 = "bfloat16", "float32"
    out = {"embed/w": ((cfg["vocab_size"], d), bf, "embed"),
           "final_norm/scale": ((d,), f32, "norm")}
    if not cfg["tie_word_embeddings"]:
        out["lm_head/w"] = ((cfg["vocab_size"], d), bf, "matrix")
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers/{i}"
        out[f"{p}/attn_norm/scale"] = ((d,), f32, "norm")
        out[f"{p}/attn/q_proj/w"] = ((h * dh, d), bf, "matrix")
        out[f"{p}/attn/q_proj/b"] = ((h * dh,), bf, "bias")
        for n in ("k_proj", "v_proj"):
            out[f"{p}/attn/{n}/w"] = ((hkv * dh, d), bf, "matrix")
            out[f"{p}/attn/{n}/b"] = ((hkv * dh,), bf, "bias")
        out[f"{p}/attn/o_proj/w"] = ((d, h * dh), bf, "matrix")
        out[f"{p}/mlp_norm/scale"] = ((d,), f32, "norm")
        out[f"{p}/mlp/gate_proj/w"] = ((ff, d), bf, "matrix")
        out[f"{p}/mlp/up_proj/w"] = ((ff, d), bf, "matrix")
        out[f"{p}/mlp/down_proj/w"] = ((d, ff), bf, "matrix")
    return out


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _linear(x, p):
    y = jnp.dot(x, p["w"].T, precision=HI)
    return y + p["b"] if "b" in p else y


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta"))
def _layer(x, p, *, dims, eps, theta):
    d, h, hkv, dh, _ = dims
    p = _f32(p)
    t = x.shape[0]
    pos = jnp.arange(t)
    a = p["attn"]
    hn = _rms(x, p["attn_norm"]["scale"], eps)
    q = _rope(_linear(hn, a["q_proj"]).reshape(t, h, dh), pos, theta)
    k = _rope(_linear(hn, a["k_proj"]).reshape(t, hkv, dh), pos, theta)
    v = _linear(hn, a["v_proj"]).reshape(t, hkv, dh)
    g = h // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HI).reshape(t, h * dh)
    x = x + jnp.dot(o, a["o_proj"]["w"].T, precision=HI)
    m = p["mlp"]
    hn = _rms(x, p["mlp_norm"]["scale"], eps)
    gate = jnp.dot(hn, m["gate_proj"]["w"].T, precision=HI)
    up = jnp.dot(hn, m["up_proj"]["w"].T, precision=HI)
    return x + jnp.dot(jax.nn.silu(gate) * up, m["down_proj"]["w"].T,
                       precision=HI)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, w, rows, *, eps):
    h = _rms(x[rows], norm.astype(jnp.float32), eps)
    return jnp.dot(h, w.astype(jnp.float32).T, precision=HI)


@jax.jit
def _embed(w, ids):
    return w[ids].astype(jnp.float32)


def logits_at(weights: dict, cfg: dict, ids: np.ndarray, rows: np.ndarray,
              t_pad: int) -> jax.Array:
    """float32 logits (len(rows), vocab) of the tokens ``ids`` at positions
    ``rows``: the logits that predict ``ids[rows + 1]``. ``ids`` is padded
    to ``t_pad`` at the end, which causal attention hides from every real
    position, so all requests share one compiled layer program."""
    if len(ids) > t_pad:
        raise ValueError(f"{len(ids)} tokens exceed t_pad {t_pad}")
    padded = np.zeros((t_pad,), np.int32)
    padded[:len(ids)] = ids
    dims = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = _embed(weights["embed"]["w"], jnp.asarray(padded))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, weights["layers"][str(i)], dims=dims, eps=eps,
                   theta=theta)
    head = (weights["embed"]["w"] if cfg["tie_word_embeddings"]
            else weights["lm_head"]["w"])
    return _head(x, weights["final_norm"]["scale"], head,
                 jnp.asarray(rows, jnp.int32), eps=eps)
