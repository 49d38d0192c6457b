"""Share of its roofline the fused paged-decode kernel reaches: see
``bench.readers.paged_attn_roofline``."""
from bench import readers


def read(run):
    return readers.paged_attn_roofline(run)
