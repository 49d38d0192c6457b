"""Reductions shared by the metric readers in ``bench/metrics/``. A reader
that finds nothing to read returns None, and the metric is left out of the
result line; a share of a roofline or a peak is never reported as 0 for
want of data."""
from __future__ import annotations

import statistics

from bench import flops, stats
from bench import trace as tr

DECODE_PROGRAM = "decode_step"       # jit_decode_step: the paged decode step
PREFILL_PROGRAM = "prefill_step"     # jit_prefill_step: the chunked prefill
PAGED_KERNEL_OP = "_call"            # the fused paged-decode Pallas kernel


def share(num: float, den: float):
    return None if not den else 100.0 * num / den


def prefill_row_fill(run):
    """Real prompt tokens over the rows x bucket width the prefill steps of
    the window computed."""
    if run.steps is None or not run.steps.prefill:
        return None
    real = sum(int(v.sum()) for _, _, _, v in run.steps.prefill)
    rows = sum(len(v) * b for _, b, _, v in run.steps.prefill)
    return share(real, rows)


def program_ms(run, contains: str):
    """Mean device time of one execution of the program, in ms."""
    if run.trace is None:
        return None
    evs = tr.module_events(run.trace, contains)
    if not evs:
        return None
    return 1e3 * statistics.fmean(e.dur for e in evs)


def mean_step_flops(run, costs) -> tuple:
    """(mean model FLOPs of one decode step, of one prefill step) over the
    steps dispatched in the window, counted by the family's ``costs``."""
    cfg = run.config
    dec = [sum(costs.decode_row_flops(cfg, int(p)) for p in pos)
           for _, pos in run.steps.decode]
    pre = [sum(costs.prefill_row_flops(cfg, int(s), int(v))
               for s, v in zip(start, valid) if v > 0)
           for _, _, start, valid in run.steps.prefill]
    return (statistics.fmean(dec) if dec else 0.0,
            statistics.fmean(pre) if pre else 0.0)


def step_mfu(run):
    """Model FLOPs of the steps that ran in the traced stretch over its
    length times the chip's peak: executions counted from the trace, each
    weighted with the mean FLOPs of its kind of step in the window."""
    costs = flops.for_config(run.config)
    if costs is None or run.trace is None or run.steps is None:
        return None
    n_dec = len(tr.module_events(run.trace, DECODE_PROGRAM))
    n_pre = len(tr.module_events(run.trace, PREFILL_PROGRAM))
    if not n_dec and not n_pre:
        return None
    f_dec, f_pre = mean_step_flops(run, costs)
    lo, hi = run.trace_window
    n_dev = len(run.trace.devices)
    return share(n_dec * f_dec + n_pre * f_pre,
                 (hi - lo) * n_dev * run.peaks.bf16_flops)


def paged_attn_roofline(run):
    """Least time of one kernel call (one layer of one decode step) at the
    chip's peaks, the larger of its FLOPs and its bytes bound, averaged over
    the decode steps of the window, over the kernel's mean device time per
    call in the trace."""
    costs = flops.for_config(run.config)
    if (costs is None or run.trace is None or run.steps is None
            or not run.steps.decode):
        return None
    calls = tr.op_events(run.trace, PAGED_KERNEL_OP)
    if not calls:
        return None
    bs = run.mix["server"]["block_size"]
    least = []
    for _, pos in run.steps.decode:
        f, b = costs.paged_attention_layer_cost(run.config,
                                                [int(p) + 1 for p in pos], bs)
        least.append(max(f / run.peaks.bf16_flops,
                         b / run.peaks.hbm_bytes_per_s))
    return share(statistics.fmean(least),
                 statistics.fmean(e.dur for e in calls))


def idle_share(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - tr.device_busy(run.trace, lo, hi) / (hi - lo))


def itl_tail(run, q: float):
    return 1e3 * stats.tail(stats.inter_token_gaps(
        run.token_times, run.t_open, run.t_close), q)

