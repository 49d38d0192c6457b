"""Random weights for a configuration, made on the device in one compiled
call from a seed, in the dtype they are served in."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Scales of the random weights: matrices are fan-in scaled, so every layer
# keeps activations of order one; the embedding's spread gives logits of a
# few units, so greedy tokens are not ties of near-equal values.
EMBED_STD = 0.05
BIAS_STD = 0.1
NORM_STD = 0.1


def _init(key, shape, dtype, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        x = z * EMBED_STD
    elif kind == "matrix":
        x = z / jnp.sqrt(jnp.float32(shape[-1]))
    elif kind == "bias":
        x = z * BIAS_STD
    elif kind == "norm":
        x = 1.0 + z * NORM_STD
    else:
        raise ValueError(kind)
    return x.astype(dtype)


def tree_from_paths(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = v
    return tree


def make_weights(layout: dict, seed: int) -> dict:
    """``layout``: path -> (shape, dtype name, init kind). Returns the
    nested tree, on the default device."""
    paths = sorted(layout)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(paths))
        return {p: _init(k, layout[p][0], jnp.dtype(layout[p][1]),
                         layout[p][2])
                for p, k in zip(paths, keys)}

    # the RBG generator: a fraction of threefry's cost on a TPU
    flat = make(jax.random.key(seed, impl="rbg"))
    return tree_from_paths(flat)
