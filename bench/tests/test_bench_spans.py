"""The serving engine's spans against what the benchmark measures today,
on a tiny served run on the CPU: the step spans hold what ``StepLog``
records around the same step calls, and a profiler trace holds every span
as an annotation on the same host line, placed by one clock offset."""
import statistics

import numpy as np
import pytest

from bench import harness, traffic
from bench import trace as tr
from bench.tests import tiny


def request(item):
    from repro.serve import Request
    return Request(rid=item.rid, tokens=item.tokens,
                   max_new_tokens=item.max_new_tokens)


@pytest.fixture(scope="module")
def server():
    """The harness's server, its engine recording spans."""
    from repro.serve.telemetry import Telemetry
    srv = harness.Server("tiny", tiny.config(), tiny.chat_mix(),
                         harness.CompileClock())
    srv.engine.telemetry = Telemetry()
    return srv


def served(server, seed, n):
    """Serve ``n`` requests of the mix; the spans of that drain."""
    eng = server.engine
    items = traffic.generate(server.mix, server.config["vocab_size"], seed)
    n_before = len(eng.telemetry.spans)
    eng.serve(server.params, [request(it) for it in items[:n]])
    return eng.telemetry.spans[n_before:]


def test_step_spans_hold_what_steplog_records(server):
    """The ``serve.step_call`` attributes of a run equal what the harness's
    ``StepLog`` records around the same engine's step calls: each prefill's
    bucket, start and valid rows, and each decode's live positions."""
    steps = harness.StepLog()
    originals = steps.install(server.engine)
    try:
        spans = served(server, 3, 16)
    finally:
        server.engine.prefill_chunk_step, server.engine.decode_step = \
            originals
    calls = [s.attrs for s in spans if s.name == "serve.step_call"]
    pre = [a for a in calls if a["kind"] == "prefill"]
    dec = [a for a in calls if a["kind"] == "decode"]
    assert len(pre) == len(steps.prefill) > 0
    assert len(dec) == len(steps.decode) > 0
    for a, (_, bucket, start, valid) in zip(pre, steps.prefill):
        assert a["bucket"] == bucket
        np.testing.assert_array_equal(a["start"], start)
        np.testing.assert_array_equal(a["valid"], valid)
        assert a["tokens"] == int(valid.sum())
        assert a["rows"] == int((valid > 0).sum())
    for a, (_, pos) in zip(dec, steps.decode):
        np.testing.assert_array_equal(a["pos"], pos)
        assert a["rows"] == len(pos)


def test_profiler_trace_holds_each_span(server, tmp_path):
    """On a CPU profiler trace of a served run, each recorded span has an
    annotation twin of its name on one host line. The ``serve.tick`` twins,
    in order, give the offset between the engine's clock and the trace's;
    with it the spans land within 1 ms of their twins. A span's clock is
    read just after its annotation opens, so a thread descheduled between
    the two (a loaded test machine) shifts that one span: the bound holds
    for 95% of them and for their median to 0.1 ms."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # as the harness traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        spans = served(server, 4, 6)
    finally:
        jax.profiler.stop_trace()
    twins: dict = {}
    for h in tr.load(tr.find_xplane(str(tmp_path))).host:
        if h.name.startswith("serve."):
            twins.setdefault(h.name, []).append(h)
    assert spans and set(twins) == {s.name for s in spans}
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert len(twins["serve.tick"]) == len(ticks)
    offset = statistics.median(h.start - s.start
                               for h, s in zip(twins["serve.tick"], ticks))
    errors = []
    for s in spans:
        twin = min(twins[s.name], key=lambda h: abs(h.start - offset
                                                    - s.start))
        errors.append(max(abs(twin.start - offset - s.start),
                          abs(twin.end - offset - s.end)))
    errors.sort()
    assert errors[len(errors) // 2] < 1e-4, errors[-5:]
    assert sum(e < 1e-3 for e in errors) >= 0.95 * len(errors), errors[-5:]
