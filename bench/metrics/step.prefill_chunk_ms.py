"""Mean device time of one execution of the chunked-prefill program."""
from bench import readers


def read(run):
    return readers.program_ms(run, readers.PREFILL_PROGRAM)
