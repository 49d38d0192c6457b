"""Share of the traced stretch in which no operation ran on the device."""
from bench import readers


def read(run):
    return readers.idle_share(run)
