"""Real prompt tokens over the rows x width the prefill steps of the window
computed, in a cell whose throughput it moves."""
from bench import readers


def read(run):
    return readers.prefill_row_fill(run)
