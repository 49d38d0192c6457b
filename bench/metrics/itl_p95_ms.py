"""95th percentile of the gaps between consecutive tokens of a request,
both delivered in the window, over every such gap."""
from bench import readers


def read(run):
    return readers.itl_tail(run, 0.95)
