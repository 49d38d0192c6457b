"""Operations and bytes the served model needs, by model family: each
family's counts live in ``bench/costs/<model_type>.py``, found by the
``model_type`` of the configuration file.

Model FLOPs count each multiply-accumulate as two operations, and only the
work the model needs: one pass of every weight matrix per processed token,
attention over the live context (causal: a token at position ``p`` attends
``p + 1`` keys), and the output head once for each row that yields a logit.
Padding, vacant batch rows and recomputation are not counted.

A cost module gives, for a configuration ``cfg``:

* ``prefill_row_flops(cfg, start, valid)``: one prompt chunk of ``valid``
  tokens starting at position ``start``;
* ``decode_row_flops(cfg, position)``: one token decoded at ``position``;
* ``paged_attention_layer_cost(cfg, lengths, block_size, kv_bytes=2)``:
  (FLOPs, HBM bytes) of the fused paged-decode kernel for one layer of one
  decode step over rows of live ``lengths``.

A family with no cost module has no counts: the metrics that need them are
left out of the result line, never reported as 0 or as another family's
count.
"""
from __future__ import annotations

from bench.harness import family_module


def for_config(cfg: dict):
    """The cost module of the configuration's family, or None where the
    family has none."""
    return family_module("costs", cfg)
