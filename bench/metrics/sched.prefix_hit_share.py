"""Prompt tokens served from the prefix cache over all prompt tokens
admitted (served from cache or prefilled), from the engine's counters."""
from bench import readers


def read(run):
    c = run.counters
    return readers.share(c["prefix_hit_tokens"],
                         c["prefix_hit_tokens"] + c["prefill_tokens"])
