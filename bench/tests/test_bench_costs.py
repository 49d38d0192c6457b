"""Step costs by model family: the Qwen2 counts are the numbers the
benchmark's single count gave before it moved into ``bench/costs/qwen2.py``,
and a family with no cost module leaves ``mfu`` and
``paged_attn_roofline`` out of the result line."""
import statistics
import types
from pathlib import Path

import numpy as np
import pytest

from bench import flops, harness, readers
from bench import trace as tr
from bench.peaks import peaks_for
from bench.tests import tiny

PREFILL_ROWS = [(0, 128), (1024, 128), (2000, 37), (4000, 96), (5, 0), (0, 8)]
DECODE_POSITIONS = [0, 15, 16, 1023, 4095]
LENGTHS = [1, 16, 17, 1024, 4096, 2500]
_QWEN2P5_3B = {
    "prefill": [713337339904.0, 751992045568.0, 227968516096.0,
                647951810560.0, 0.0, 45025460224.0],
    "decode": [6171688960.0, 6176112640.0, 6176407552.0, 6473383936.0,
               7379353600.0],
    "paged_16": (62701568.0, 7929856.0),
    "paged_32_kv1": (62701568.0, 4014080.0),
}
# FLOPs and bytes of the rows above, from the counts as they were
EXPECTED = {
    "qwen2p5_3b": _QWEN2P5_3B,
    "qwen2p5_3b_amp": _QWEN2P5_3B,
    "tiny": {
        "prefill": [23166976.0, 90275840.0, 43769344.0, 213213184.0, 0.0,
                    1263616.0],
        "decode": [213504.0, 221184.0, 221696.0, 737280.0, 2310144.0],
        "paged_16": (1959424.0, 986624.0),
        "paged_32_kv1": (1959424.0, 497152.0),
    },
}
TRACE = Path(__file__).parent / "data" / "small.xplane.pb"


def config_file(name: str) -> dict:
    if name == "tiny":
        return tiny.config()
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_qwen2_counts_are_unchanged(name):
    cfg = config_file(name)
    costs = flops.for_config(cfg)
    want = EXPECTED[name]
    assert [costs.prefill_row_flops(cfg, s, v)
            for s, v in PREFILL_ROWS] == want["prefill"]
    assert [costs.decode_row_flops(cfg, p)
            for p in DECODE_POSITIONS] == want["decode"]
    assert costs.paged_attention_layer_cost(cfg, LENGTHS, 16) == \
        want["paged_16"]
    assert costs.paged_attention_layer_cost(cfg, LENGTHS, 32, 1) == \
        want["paged_32_kv1"]


def traced_run(cfg: dict):
    """A run of the recorded small trace (three decode and three prefill
    programs, three kernel calls) with fixed steps: the decode positions
    and prefill rows above."""
    trace = tr.load(str(TRACE))
    steps = harness.StepLog()
    steps.decode = [(0.0, np.array(DECODE_POSITIONS)),
                    (1.0, np.array(DECODE_POSITIONS[:2]))]
    start, valid = (np.array(x) for x in zip(*PREFILL_ROWS))
    steps.prefill = [(0.0, 128, start, valid)]
    return types.SimpleNamespace(config=cfg, mix=tiny.chat_mix(),
                                 trace=trace, trace_window=tr.span(trace),
                                 steps=steps, peaks=peaks_for("TPU v5 lite"))


def test_readers_count_by_the_family():
    """``mfu`` and ``paged_attn_roofline`` of a Qwen2 run are the counts
    above put through the readers' arithmetic."""
    run = traced_run(config_file("qwen2p5_3b"))
    want = EXPECTED["qwen2p5_3b"]
    dec = statistics.fmean([sum(want["decode"]), sum(want["decode"][:2])])
    pre = sum(want["prefill"])
    lo, hi = run.trace_window
    peak_flops, peak_bytes = run.peaks.bf16_flops, run.peaks.hbm_bytes_per_s
    assert readers.step_mfu(run) == pytest.approx(
        100 * (3 * dec + 3 * pre) / ((hi - lo) * peak_flops), rel=1e-12)
    costs = flops.for_config(run.config)
    least = [max(f / peak_flops, b / peak_bytes) for f, b in (
        costs.paged_attention_layer_cost(run.config, [p + 1 for p in pos],
                                         16)
        for pos in (DECODE_POSITIONS, DECODE_POSITIONS[:2]))]
    kernel = statistics.fmean(e.dur for e in tr.op_events(run.trace,
                                                          "_call"))
    assert readers.paged_attn_roofline(run) == pytest.approx(
        100 * statistics.fmean(least) / kernel, rel=1e-12)


@pytest.mark.parametrize("family", ["no_such_family", "../qwen2", ""])
def test_a_family_without_costs_reports_nothing(family):
    cfg = dict(config_file("qwen2p5_3b"), model_type=family)
    assert flops.for_config(cfg) is None
    run = traced_run(cfg)
    assert readers.step_mfu(run) is None
    assert readers.paged_attn_roofline(run) is None
    data = harness.RunData(workload="w", config=cfg, mix=run.mix, seconds=1,
                           setup_s=1, t_open=0, t_close=1, token_times={},
                           counters={}, peaks=run.peaks, steps=run.steps,
                           trace=run.trace, trace_window=run.trace_window)
    specs = [{"name": n, "unit": "%"} for n in ("mfu", "paged_attn_roofline",
                                                "device.idle_share")]
    assert set(harness.read_metrics(specs, data)) == {"device.idle_share"}
