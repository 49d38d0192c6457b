"""Live KV tokens summed over decode steps, over the pool's capacity summed
over the same steps, from the engine's counters."""
from bench import readers


def read(run):
    c = run.counters
    return readers.share(c["decode_live_token_steps"],
                         c["decode_capacity_token_steps"])
