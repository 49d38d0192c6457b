"""Ops the solved AMP plan quantizes, over every quantizable op."""
from bench import readers


def read(run):
    if run.plan is None:
        return None
    return readers.share(run.plan["n_quantized"], run.plan["n_ops"])
