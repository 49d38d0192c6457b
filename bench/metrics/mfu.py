"""Model FLOPs of the whole serving step over the traced stretch, as a share
of the chip's bf16 peak: see ``bench.readers.step_mfu``."""
from bench import readers


def read(run):
    return readers.step_mfu(run)
