"""``correct`` through the harness, at a tiny size on the CPU: a sound run
is correct, each fault of the timed path that a serving cell can have makes
it not correct, and the control reads far above the program.

These drive ``harness.execute`` (all of a run but the look for a chip) with
the tiny configuration of ``bench/tests/tiny.py``; its limit sits between
the program's readings and the control's at this size."""
import time

import numpy as np
import pytest

from bench import check, harness
from bench.tests import tiny

# at this size the program reads a mean gap of 0 to 2e-4 and the control
# 4e-3 to 1.1e-2 (CPU, six seeds)
LIMITS = {"mean_logit_gap": 0.002, "min_sampled_tokens": 16}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def clock():
    return harness.CompileClock()


@pytest.fixture(scope="module")
def spec():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def run(spec, clock, workload="qwen2p5-3b.chat", mix=None, seed=2**33 + 5):
    mix = mix or tiny.chat_mix()
    return harness.execute(spec, workload, tiny.config(), mix, LIMITS, seed,
                           4.0, False, None, DEVICE, time.perf_counter(),
                           clock)


def test_sound_run_is_correct(spec, clock):
    result, lines = run(spec, clock)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    gap = result["checks"]["mean_logit_gap"]
    assert gap["limit"] == LIMITS["mean_logit_gap"] and gap["value"] >= 0
    assert lines[0].startswith("check mean_logit_gap ")


def _altered_token(monkeypatch):
    """A token altered where it is produced: the engine's greedy pick."""
    import repro.serve.engine as engine
    vocab = tiny.config()["vocab_size"]
    real = engine.greedy_next_token
    monkeypatch.setattr(engine, "greedy_next_token",
                        lambda logits: (real(logits) + 1) % vocab)


def _state_unchanged(monkeypatch):
    """A decode step that returns its KV state unchanged."""
    import repro.launch.steps as steps
    real = steps.make_paged_decode_step

    def make(model, mp=None, paged_attn="fused"):
        step = real(model, mp=mp, paged_attn=paged_attn)

        def unchanged(params, caches, token, pos, block_tables):
            logits, _ = step(params, caches, token, pos, block_tables)
            return logits, caches
        return unchanged
    monkeypatch.setattr(steps, "make_paged_decode_step", make)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_in_the_timed_path_is_not_correct(spec, clock, monkeypatch,
                                               fault):
    fault(monkeypatch)
    result, _ = run(spec, clock)
    assert result["correct"] is False
    assert result["checks"]["mean_logit_gap"]["value"] > \
        LIMITS["mean_logit_gap"]


def test_control_reads_far_above_the_program(clock):
    """The program's own lower-precision path (every op in fp8) on the same
    prompts and served tokens, on three seeds: the harness's verdict with
    the cell's limits finds the program correct and the control not."""
    mix = tiny.chat_mix()
    cfg = tiny.config()
    server = harness.Server("tiny", cfg, mix, clock)
    first = check.control_first(server)
    rows_pad = mix["answer_tokens"]["max"]
    for seed in (11, 12, 13):
        data, results, items = server.run(seed, 2.0, False)
        server.release()
        sample = check.sample(check.finished(results, items),
                              np.random.default_rng([seed, 3]),
                              **mix["check"])

        def ref(ids, rows):
            return server.ref.logits_at(server.params, cfg, ids, rows,
                                        mix["server"]["max_len"])
        program = check.summary(check.reference_gaps(ref, sample, rows_pad))
        control = check.summary(check.control_gaps(ref, first, sample,
                                                   rows_pad))
        assert harness.verdict(program, LIMITS, data.counters)[0] is True
        assert harness.verdict(control, LIMITS, data.counters)[0] is False
        assert control["tokens"] == program["tokens"] >= \
            LIMITS["min_sampled_tokens"]
        assert control["mean_logit_gap"] >= 3 * program["mean_logit_gap"]


@pytest.mark.parametrize("keep_plan,want", [
    (False, {"a": "fp4_e2m1", "b": "fp8_e4m3", "c": "fp8_e4m3"}),
    (True, {"a": "fp8_e4m3", "b": "fp8_e4m3", "c": "fp8_e4m3"})],
    ids=["step_down", "plan_all_fp8"])
def test_control_assignment(keep_plan, want):
    """Under a plan that puts ``a`` in fp8: every op one step down, or only
    the plan's bf16 ops."""
    stated = {"a": "fp8_e4m3", "b": "bf16"}
    assert check.control_assignment("abc", stated, keep_plan) == want
