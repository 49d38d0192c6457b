"""The traffic generator gives every seed the same work."""
import hashlib
from pathlib import Path

import numpy as np
import pytest

from bench import harness, traffic
from bench.tests import tiny

DATA = Path(__file__).parent / "data"
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
    plan = traffic.session_plan(mix)
    sys_lens = traffic.prefix_lengths(mix) + [0]     # index -1: none
    assert all(u["min"] <= len(it.tokens) - sys_lens[plan[it.session][0]]
               <= u["max"] for it in firsts)


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


def _items_sha256(items) -> str:
    h = hashlib.sha256()
    for it in items:
        h.update(np.asarray(it.tokens, "<i4").tobytes())
        h.update(np.asarray([it.max_new_tokens, it.session, it.turn],
                            "<i8").tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [7, 2**33 + 11])
def test_chat_mix_gives_the_golden_requests(seed):
    """The chat mix yields, token for token, the requests it yielded before
    prefix lengths could be drawn (``data/chat_golden.json``)."""
    gold = harness.load_json(DATA / "chat_golden.json")
    mix = real_mix("chat")
    items = traffic.generate(mix, gold["vocab"], seed)
    want = gold["seeds"][str(seed)]
    assert len(items) == want["n_items"]
    assert _items_sha256(items) == want["items_sha256"]
    h = hashlib.sha256()
    for p in traffic.system_prompts(mix, gold["vocab"], seed):
        h.update(np.asarray(p, "<i4").tobytes())
    assert h.hexdigest() == want["system_prompts_sha256"]
    assert traffic.length_profile(items) == \
        [tuple(x) for x in gold["length_profile"]]


def document_mix() -> dict:
    """The tiny chat mix with shared prefixes of drawn lengths: documents
    asked about in sessions of two or three turns."""
    mix = tiny.chat_mix()
    mix["system_prompts"] = {"count": 5, "cached_at_start": True,
                             "tokens": {"median": 96, "sigma": 0.5,
                                        "min": 48, "max": 200}}
    return mix


def test_drawn_prefix_lengths_sit_at_their_quantiles():
    mix = document_mix()
    dist = mix["system_prompts"]["tokens"]
    lens = traffic.prefix_lengths(mix)
    assert lens == [traffic.length_at((i + 0.5) / 5, dist) for i in range(5)]
    assert all(dist["min"] <= n <= dist["max"] for n in lens)
    assert len(set(lens)) == 5
    prompts = traffic.system_prompts(mix, 512, 2**33 + 3)
    assert [len(p) for p in prompts] == lens
    # an integer gives every prefix that length
    assert traffic.prefix_lengths(tiny.chat_mix()) == [64, 64]


@pytest.mark.parametrize("seed", [4, 2**33 + 17])
def test_document_sessions_open_with_their_document(seed):
    """Every turn of a session starts with exactly its document's tokens,
    each session is fitted to ``max_len`` with its own document's length,
    and the lengths do not depend on the seed."""
    mix = document_mix()
    docs = traffic.system_prompts(mix, 512, seed)
    plan = traffic.session_plan(mix)
    items = traffic.generate(mix, 512, seed)
    assert items and {it.session for it in items} == \
        {s for s, (_, pairs) in enumerate(plan) if pairs}
    max_len = mix["server"]["max_len"]
    for it in items:
        doc = docs[plan[it.session][0]]
        assert np.array_equal(it.tokens[:len(doc)], doc)
        assert len(it.tokens) + it.max_new_tokens <= max_len
    # the plan fits each session with its own document's length
    lens = traffic.prefix_lengths(mix)
    for sys_idx, pairs in plan:
        assert lens[sys_idx] + sum(u + a for u, a in pairs) <= max_len
    assert traffic.length_profile(items) == traffic.length_profile(
        traffic.generate(mix, 512, seed + 1))
