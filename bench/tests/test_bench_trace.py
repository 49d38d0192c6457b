"""The trace reduction, on a small trace recorded on a TPU v5e: a jitted
matmul program (``prefill_step``) and the fused paged-decode kernel
(``decode_step``), three calls each. The profiler's own Perfetto export of
the same trace is the second reading."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def small():
    return tr.load(str(DATA / "small.xplane.pb"))


@pytest.fixture(scope="module")
def perfetto():
    d = json.load(gzip.open(DATA / "small.trace.json.gz"))
    evs = d["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in evs
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    out = {}
    for e in evs:
        if e.get("ph") == "X" and procs.get(e["pid"]) == "/device:TPU:0":
            line = threads[(e["pid"], e["tid"])]
            out.setdefault(line, []).append(
                (e["name"], e["ts"] * 1e-6, e["dur"] * 1e-6))
    return out


def test_busy_and_idle(small, perfetto):
    lo, hi = tr.span(small)
    ops = perfetto["XLA Ops"]
    assert lo == pytest.approx(min(t for _, t, _ in ops), abs=1e-8)
    assert hi == pytest.approx(max(t + d for _, t, d in ops), abs=1e-8)
    assert small.devices == ["/device:TPU:0"]
    busy = tr.device_busy(small, lo, hi)
    merged = tr.union((t, t + d) for _, t, d in ops)
    # ProfileData rounds times to whole nanoseconds, the export keeps
    # picoseconds: the two readings agree to about 1e-5
    assert busy == pytest.approx(sum(e - s for s, e in merged), rel=1e-4)
    # known values of this trace: 401.8 us busy in a 16.33 ms window
    assert busy == pytest.approx(401.757e-6, rel=1e-4)
    assert hi - lo == pytest.approx(16.328262e-3, rel=1e-6)
    assert 1 - busy / (hi - lo) == pytest.approx(0.97539, abs=1e-4)


def test_kernel_and_program_times(small, perfetto):
    calls = tr.op_events(small, "_call")
    want = [d for n, _, d in perfetto["XLA Ops"] if n.startswith("_call")]
    assert len(calls) == 3
    assert sum(e.dur for e in calls) == pytest.approx(sum(want), rel=1e-4)
    assert sum(e.dur for e in calls) == pytest.approx(102.2474e-6, rel=1e-4)
    dec = tr.module_events(small, "decode_step")
    pre = tr.module_events(small, "prefill_step")
    assert len(dec) == len(pre) == 3
    mods = perfetto["XLA Modules"]
    assert sum(e.dur for e in pre) == pytest.approx(
        sum(d for n, _, d in mods if "prefill_step" in n), rel=1e-4)


def test_op_names_and_breakdown(small):
    assert tr.base_name("%_call.1 = bf16[4,2,8,128] custom-call(s32[4,8] "
                        "%bt)") == "_call"
    assert tr.base_name("jit_decode_step(17986311345917834216)") == \
        "jit_decode_step"
    top = tr.top_ops(small)
    assert [n for n, _ in top[:3]] == ["convolution_tanh_fusion", "fusion",
                                       "_call"]
    lo, hi = tr.span(small)
    gaps = tr.idle_gaps(small, lo, hi, top=3)
    assert len(gaps) == 3 and all(g > 3e-3 for _, g in gaps)
