"""The traffic generator gives every seed the same work."""
import numpy as np
import pytest

from bench import harness, traffic
from bench.tests import tiny

MIXES = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))


def real_mix(name):
    return harness.load_json(harness.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_lengths_other_tokens_for_every_seed(name):
    mix = real_mix(name)
    a = traffic.generate(mix, 151936, 7)
    b = traffic.generate(mix, 151936, 2**33 + 11)
    assert traffic.length_profile(a) == traffic.length_profile(b)
    assert len(a) == len(b)
    # the same session structure: turns per session and their lengths
    struct = lambda items: sorted((it.session, it.turn, len(it.tokens),
                                   it.max_new_tokens) for it in items)
    assert struct(a) == struct(b)
    # token content comes from the seed; the order is the same
    assert [(it.session, it.turn) for it in a] == \
        [(it.session, it.turn) for it in b]
    assert all(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_the_mix_and_the_pool(name):
    mix = real_mix(name)
    items = traffic.generate(mix, 151936, 3)
    u, a = mix["user_tokens"], mix["answer_tokens"]
    for it in items:
        assert it.tokens.dtype == np.int32
        assert len(it.tokens) + it.max_new_tokens <= mix["server"]["max_len"]
        assert 1 <= it.max_new_tokens <= a["max"]
        assert 0 <= it.tokens.min() and it.tokens.max() < 151936
    firsts = [it for it in items if it.turn == 0]
    sys_len = mix["system_prompts"]["tokens"]
    assert all(u["min"] <= len(it.tokens) - sys_len <= u["max"]
               for it in firsts)


def test_chat_turn_extends_its_session_history():
    mix = real_mix("chat")
    items = traffic.generate(mix, 151936, 5)
    by = {(it.session, it.turn): it for it in items}
    for (s, t), it in by.items():
        if t:
            prev = by[(s, t - 1)].tokens
            assert np.array_equal(it.tokens[:len(prev)], prev)
    # a turn reaches the queue a full round of lanes after the previous
    # turn of its session (until the sessions run out, far past any window)
    pos = {(it.session, it.turn): it.rid for it in items}
    head = len(items) // 2
    assert all(pos[(s, t)] - pos[(s, t - 1)] >= mix["arrivals"]["lanes"]
               for (s, t) in pos if t and pos[(s, t)] < head)


def test_first_wave_stands_for_requests_in_flight():
    mix = real_mix("chat")
    n = mix["server"]["n_slots"]
    full = dict(mix, arrivals=dict(mix["arrivals"],
                                   first_wave_in_flight=False))
    cut = traffic.generate(mix, 151936, 9)
    whole = traffic.generate(full, 151936, 9)
    assert [it.max_new_tokens for it in cut[n:]] == \
        [it.max_new_tokens for it in whole[n:]]
    ranked = sorted(whole[:n], key=lambda it: (it.max_new_tokens,
                                               it.session))
    want = sorted(max(1, -(-it.max_new_tokens * (r + 1) // n))
                  for r, it in enumerate(ranked))
    assert sorted(it.max_new_tokens for it in cut[:n]) == want


def test_no_arrival_schedule_in_engine_ticks():
    """Requests reach the engine with no tick arrival: the backlog is due
    at the start, on the benchmark's clock."""
    import inspect

    from repro.serve import Request
    src = inspect.getsource(harness.Server)
    assert "arrival=" not in src
    assert Request(0, np.zeros(4, np.int32), 2).arrival == 0
    with pytest.raises(ValueError, match="arrival mode"):
        mix = tiny.chat_mix()
        traffic.generate(dict(mix, arrivals=dict(mix["arrivals"],
                                                 mode="ticks")), 512, 1)
