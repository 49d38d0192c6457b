"""Every output token delivered in the window (finished request or not),
over the window's length on the benchmark's clock."""
from bench import stats


def read(run):
    return stats.rate(run.token_times, run.t_open, run.t_close)
