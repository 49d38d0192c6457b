"""Mean device time of one execution of the paged decode program."""
from bench import readers


def read(run):
    return readers.program_ms(run, readers.DECODE_PROGRAM)
