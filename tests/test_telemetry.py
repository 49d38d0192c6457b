"""The serving engine's spans and live counters (``repro.serve.telemetry``):
the recorder changes no token and no counter, records nothing when off,
nests its spans inside the host loop's ticks, and the live counters end
the drain at the values its summary reports."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.models.registry import get_model
from repro.serve import ContinuousBatchingEngine, Request
from repro.serve.telemetry import Telemetry

# counters that read a clock, or how the consumer thread's wake-ups fell
TIMED = {"wall_tokens_per_s", "host_blocked_s", "host_blocked_s_per_step",
         "drain_wait_s", "decode_stall_p50_s", "decode_stall_p99_s"}
BATCHING = {"n_readbacks", "readback_batch_max", "readback_batch_mean",
            "steps_in_flight_peak"}
PRODUCER = {"serve.admit", "serve.prepare", "serve.step_call",
            "serve.emit_wait"}
WAITS = ("serve.step_call", "serve.emit_wait")


@pytest.fixture(scope="module")
def model():
    return get_model("llama3_1b", smoke=True)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


def _requests():
    """Prompts that share a 24-token prefix (prefix-cache hits), long enough
    to prefill in several chunks, arriving over a few ticks."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 500, 24)
    return [Request(rid=i, tokens=np.concatenate(
        [shared, rng.integers(0, 500, 4 + 5 * i)]).astype(np.int32),
        max_new_tokens=3 + i % 4, arrival=i // 2) for i in range(6)]


def _preempting_requests():
    """Three requests, one live at a time in a 6-block pool; the
    higher-priority latecomer evicts a live one."""
    rng = np.random.default_rng(29)
    return [Request(rid=i, tokens=rng.integers(0, 500, 12).astype(np.int32),
                    max_new_tokens=8, priority=int(i == 2), arrival=i)
            for i in range(3)]


def _engine(model, telemetry=None):
    return ContinuousBatchingEngine(model, n_slots=3, max_len=96,
                                    block_size=8, chunk_len=16,
                                    telemetry=telemetry)


def _covered(spans, lo, hi):
    """Seconds of [lo, hi) that the spans cover, each instant once."""
    total, reach = 0.0, lo
    for s in sorted(spans, key=lambda s: s.start):
        a, b = max(s.start, reach), min(s.end, hi)
        if b > a:
            total += b - a
        reach = max(reach, min(s.end, hi))
    return total


@pytest.fixture(scope="module")
def recorded(model, params):
    """One async drain with the recorder on: (engine, summary, spans)."""
    tel = Telemetry()
    eng = _engine(model, tel)
    summary = eng.serve(params, _requests())
    return eng, summary, tel.spans


@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_recorder_changes_no_token_and_no_counter(model, params, monkeypatch,
                                                  sync):
    opened = []
    real = jax.profiler.TraceAnnotation

    class Counted(real):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)

    off = _engine(model).serve(params, _requests(), sync=sync)
    assert not [n for n in opened if n.startswith("serve.")]
    tel = Telemetry()
    on = _engine(model, tel).serve(params, _requests(), sync=sync)
    assert {n for n in opened if n.startswith("serve.")} == {
        "serve.tick"} | PRODUCER
    assert tel.spans and all(s.end is not None for s in tel.spans)
    # every span is the producer's: the calling thread
    assert {s.thread for s in tel.spans} == {threading.current_thread().name}

    assert sorted(on.results) == sorted(off.results) == list(range(6))
    for rid in off.results:
        np.testing.assert_array_equal(on.tokens_for(rid), off.tokens_for(rid))
    skip = TIMED if sync else TIMED | BATCHING
    assert {k: v for k, v in on.counters.items() if k not in skip} == \
        {k: v for k, v in off.counters.items() if k not in skip}
    assert on.counters["prefix_hit_tokens"] > 0
    assert on.counters["prefill_chunks"] > len(off.results)
    assert on.n_steps == off.n_steps


def test_spans_nest_inside_ticks(recorded):
    _, summary, spans = recorded
    by_id = {s.id: s for s in spans}
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert ticks and all(s.parent == 0 for s in ticks)
    # ticks tile the loop: each opens where the one before it closed
    for a, b in zip(ticks, ticks[1:]):
        assert a.end <= b.start < a.end + 0.5
    for s in spans:
        assert s.end >= s.start
        assert s.name in PRODUCER or s.name == "serve.tick", s
        if s.name in PRODUCER:
            top = s
            while top.parent:
                parent = by_id[top.parent]
                assert parent.start <= top.start and top.end <= parent.end
                top = parent
            assert top.name == "serve.tick", s
    calls = [s for s in spans if s.name == "serve.step_call"]
    assert [s.attrs["seq"] for s in calls] == list(range(len(calls)))
    kinds = [s.attrs["kind"] for s in calls]
    assert kinds.count("decode") == summary.n_steps
    assert kinds.count("prefill") == summary.counters["prefill_chunks"]
    # a step's delivery wait follows its call in the same tick; every
    # decode step is handed to delivery, a prefill step where it finished
    # a prompt
    call_of = {s.attrs["seq"]: s for s in calls}
    waits = [s for s in spans if s.name == "serve.emit_wait"]
    for w in waits:
        c = call_of[w.attrs["seq"]]
        assert w.parent == c.parent and c.end <= w.start
    decodes = {s.attrs["seq"] for s in calls if s.attrs["kind"] == "decode"}
    assert decodes <= {w.attrs["seq"] for w in waits}
    assert len(waits) == len({w.attrs["seq"] for w in waits})


def test_shares_of_a_window_add_up(recorded):
    """Over a window cut out of the middle of the drain, the host loop's
    own time (ticks less step calls and delivery waits), the step calls
    and the delivery waits add up to the window within 1%: the waits never
    overlap, so none is counted twice, and the ticks cover the window."""
    _, _, spans = recorded
    ticks = [s for s in spans if s.name == "serve.tick"]
    lo = ticks[0].start + 0.25 * (ticks[-1].end - ticks[0].start)
    hi = ticks[0].start + 0.75 * (ticks[-1].end - ticks[0].start)
    waits = sorted((s for s in spans if s.name in WAITS),
                   key=lambda s: s.start)
    for a, b in zip(waits, waits[1:]):
        assert a.end <= b.start
    in_ticks = _covered(ticks, lo, hi)
    parts = {name: _covered([s for s in spans if s.name == name], lo, hi)
             for name in WAITS}
    loop = in_ticks - sum(parts.values())
    assert loop >= 0 and all(v > 0 for v in parts.values()), parts
    # the three shares are the ticks' time: outside them is under 1%
    assert hi - lo - in_ticks < 0.01 * (hi - lo)


@pytest.mark.parametrize("case", ["prefix_hits", "preemption"])
def test_live_counters_end_at_the_summary(model, params, case):
    """A snapshot is a copy; the opening snapshot plus the counts between
    two snapshots is the closing one; the counters at the drain's end are
    the summary's, prefix hits and preemptions included."""
    if case == "prefix_hits":
        eng, reqs, marks = _engine(model), _requests(), (1, 4)
    else:
        eng = ContinuousBatchingEngine(model, n_slots=2, max_len=32,
                                       block_size=4, n_blocks=6)
        reqs, marks = _preempting_requests(), (0, 1)
    snaps = []

    def on_token(rid, idx, tok):
        if idx == 0 and rid in marks and len(snaps) < 2:
            snaps.append(eng.live_counters.snapshot())

    summary = eng.serve(params, reqs, on_token=on_token)
    end = eng.live_counters.snapshot()
    opened, closed = snaps
    assert set(opened) == set(closed) == set(end)
    delta = {k: closed[k] - opened[k] for k in closed}
    assert {k: opened[k] + delta[k] for k in opened} == closed
    assert all(delta[k] >= 0 for k in delta)
    assert all(closed[k] <= end[k] for k in end)
    c = summary.counters
    for k in ("prefill_chunks", "prefill_tokens", "prefix_hit_tokens",
              "decode_live_token_steps", "decode_capacity_token_steps",
              "preemptions", "n_readbacks"):
        assert end[k] == c[k], k
    assert end["decode_steps"] == summary.n_steps
    assert end["emit_wait_s"] == c["host_blocked_s"]
    assert end["drain_wait_s"] == c["drain_wait_s"]
    assert end["steps_read_back"] / end["n_readbacks"] == \
        c["readback_batch_mean"]
    assert "cached_blocks_final" not in c
    if case == "prefix_hits":
        assert delta["decode_steps"] > 0 and end["prefix_hit_tokens"] > 0
    else:
        assert end["preemptions"] >= 1


def test_sync_decode_time_counts_delivery(model, params):
    """In sync mode a decode step's time runs from its dispatch until its
    tokens are delivered: ``on_token`` callbacks that sleep lengthen it by
    their sleep, against the same drain without them."""
    nap = 0.03

    def on_token(rid, idx, tok):
        if idx > 0:
            time.sleep(nap)

    plain = _engine(model).serve(params, _requests(), sync=True)
    slow = _engine(model).serve(params, _requests(), sync=True,
                                on_token=on_token)
    n_decoded = sum(len(r.tokens) - 1 for r in slow.results.values())
    assert n_decoded > 0
    assert slow.decode_s - plain.decode_s >= 0.5 * nap * n_decoded
    assert slow.tokens_per_s < plain.tokens_per_s


def test_kernel_step_counters_follow_the_decode_lengths(model, params):
    """``decode_kernel_grid_steps`` and ``decode_kernel_copy_steps``: per
    decode step, one layer's kernel runs 2 phases x ceil(n_pages / P) grid
    steps a row, and copies in the steps of each row's groups that hold a
    live page; vacant rows read one page (length 1). Counted by hand from
    the positions each decode step call records, and the kernel's P."""
    from repro.kernels.paged_attention import pages_per_step
    n_slots, block, max_len = 3, 8, 320
    tel = Telemetry()
    eng = ContinuousBatchingEngine(model, n_slots=n_slots, max_len=max_len,
                                   block_size=block, chunk_len=64,
                                   telemetry=tel)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, tokens=rng.integers(0, 500, n).astype(np.int32),
                    max_new_tokens=12, arrival=i)
            for i, n in enumerate((120, 126, 140, 60))]
    eng.serve(params, reqs)
    end = eng.live_counters.snapshot()
    n_pages = max_len // block
    P = pages_per_step(block, n_pages)
    span = P * block
    assert (P, -(-n_pages // P)) == (16, 3)
    steps = copies = 0
    crossed = False
    for sp in tel.spans:
        if sp.name != "serve.step_call" or sp.attrs["kind"] != "decode":
            continue
        lengths = [int(p) + 1 for p in sp.attrs["pos"]]
        lengths += [1] * (n_slots - len(lengths))
        crossed |= max(lengths) > span
        steps += n_slots * 2 * -(-n_pages // P)
        copies += sum(2 * -(-n // span) for n in lengths)
    assert crossed, "no row reached a second group of pages"
    assert end["decode_kernel_grid_steps"] == steps > 0
    assert end["decode_kernel_copy_steps"] == copies
