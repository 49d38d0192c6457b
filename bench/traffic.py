"""The one traffic generator. A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Every length is fixed by the mix alone, so every seed gives a cell the same
work:

* lengths are taken at fixed quantiles of each length distribution
  (log-normal, clipped), visited in a fixed stride order so that every
  ``quantile_block`` consecutive turns hold each quantile exactly once;
* sessions (system prompt, number of turns, per-turn lengths) are built
  from those lengths in a fixed order, and a session ends early where its
  next turn would not fit ``server.max_len``.

The seed picks token content and nothing else: the order of requests is
fixed too, so every seed gives the window the same work and the runs of
different seeds spread no more than two runs of one seed. A turn's prompt is
the session's system prompt, every earlier turn and a synthetic answer of
each earlier turn's drawn length, then the new user turn: the engine's own
answers cannot be known up front.

Arrivals (``arrivals.mode``): ``backlog`` alone, every request due when
serving starts. Sessions run in ``lanes`` round-robin lanes, so a turn
reaches the queue ``lanes`` requests after the previous turn of its
session. With ``first_wave_in_flight`` the first ``n_slots`` requests
stand for requests already being served: ranked by answer length, the r-th
keeps ``ceil(answer * (r + 1) / n_slots)`` tokens, so slots start freeing
at the steady rate from the first step.

A "system prompt" is any prefix that sessions share: a system prompt, or a
document that is asked about several times. Session ``s`` opens with
prefix ``s % count``. ``system_prompts.tokens`` is a length, the same for
every prefix, or a length distribution as ``user_tokens`` is: prefix ``i``
then takes the length at quantile ``(i + 0.5) / count``, so the mix alone
fixes the lengths here too. With ``system_prompts.cached_at_start`` the
prefixes are served once before serving starts (set-up), as a deployment
that has served them before holds them in its prefix cache.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Item:
    rid: int
    tokens: np.ndarray          # (T,) int32 prompt
    max_new_tokens: int
    session: int                # index of the session in the fixed plan
    turn: int


def length_at(level: float, dist: dict) -> int:
    """Clipped log-normal length at quantile ``level`` of ``dist``
    (``median``, ``sigma``, ``min``, ``max``)."""
    x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(level))
    return int(min(max(round(x), dist["min"]), dist["max"]))


def prefix_lengths(mix: dict) -> list:
    """Token length of each shared prefix (``system_prompts``)."""
    sys_cfg = mix["system_prompts"]
    n, tokens = sys_cfg["count"], sys_cfg["tokens"]
    if isinstance(tokens, dict):
        return [length_at((i + 0.5) / n, tokens) for i in range(n)]
    return [int(tokens)] * n


def session_plan(mix: dict) -> list:
    """Per session: (system prompt index or -1, [(user, answer), ...]).
    Depends on the mix only."""
    k = mix["quantile_block"]
    stride_u, stride_a = mix["user_stride"], mix["answer_stride"]
    for s in (stride_u, stride_a):
        if math.gcd(s, k) != 1:
            raise ValueError(f"stride {s} must be coprime with "
                             f"quantile_block {k}")
    sys_lens = prefix_lengths(mix)
    n_sys = len(sys_lens)
    max_len = mix["server"]["max_len"]
    turns = mix["turns"]
    plan, j = [], 0
    for s in range(mix["sessions"]):
        sys_idx = s % n_sys if n_sys else -1
        history = sys_lens[sys_idx] if n_sys else 0
        pairs = []
        for _ in range(turns[s % len(turns)]):
            u = length_at(((j * stride_u) % k + 0.5) / k, mix["user_tokens"])
            a = length_at(((j * stride_a) % k + 0.5) / k,
                          mix["answer_tokens"])
            j += 1
            if history + u + a > max_len:
                break
            pairs.append((u, a))
            history += u + a
        plan.append((sys_idx, pairs))
    return plan


def _queue(mix: dict, plan: list) -> list:
    """(session, turn) in queue order: round-robin over lanes, each lane
    taking the next session when its current one ends."""
    lanes = mix["arrivals"].get("lanes", 1)
    todo = [s for s in range(len(plan)) if plan[s][1]]
    nxt = 0
    cur = []
    for _ in range(lanes):
        if nxt < len(todo):
            cur.append([todo[nxt], 0])
            nxt += 1
    out = []
    while cur:
        keep = []
        for lane in cur:
            s, t = lane
            out.append((s, t))
            if t + 1 < len(plan[s][1]):
                keep.append([s, t + 1])
            elif nxt < len(todo):
                keep.append([todo[nxt], 0])
                nxt += 1
        cur = keep
    return out


def system_prompts(mix: dict, vocab: int, seed: int) -> list:
    return [np.random.default_rng([seed, 0, i]).integers(
        0, vocab, n, dtype=np.int32)
        for i, n in enumerate(prefix_lengths(mix))]


def generate(mix: dict, vocab: int, seed: int) -> list:
    """The run's requests, in queue order (``rid`` = position)."""
    plan = session_plan(mix)
    sys_prompts = system_prompts(mix, vocab, seed)
    content = {}

    def turns_of(s):
        if s not in content:
            rng = np.random.default_rng([seed, 1, s])
            content[s] = [(rng.integers(0, vocab, u, dtype=np.int32),
                           rng.integers(0, vocab, a, dtype=np.int32))
                          for u, a in plan[s][1]]
        return content[s]

    items = []
    for rid, (s, t) in enumerate(_queue(mix, plan)):
        sys_idx, pairs = plan[s]
        parts = [sys_prompts[sys_idx]] if sys_idx >= 0 else []
        body = turns_of(s)
        for user, answer in body[:t]:
            parts += [user, answer]
        parts.append(body[t][0])
        items.append(Item(rid=rid, tokens=np.concatenate(parts),
                          max_new_tokens=pairs[t][1], session=s, turn=t))
    arr = mix["arrivals"]
    if arr["mode"] != "backlog":
        raise ValueError(f"unknown arrival mode {arr['mode']!r}")
    if arr.get("first_wave_in_flight"):
        items = _cut_first_wave(items, mix["server"]["n_slots"])
    return items


def _cut_first_wave(items: list, n_slots: int) -> list:
    head = items[:n_slots]
    ranked = sorted(head, key=lambda it: (it.max_new_tokens, it.session))
    cut = {it.rid: max(1, math.ceil(it.max_new_tokens * (r + 1) / n_slots))
           for r, it in enumerate(ranked)}
    return [dataclasses.replace(it, max_new_tokens=cut[it.rid])
            if it.rid in cut else it for it in items]


def length_profile(items: list) -> list:
    """The sorted multiset of (prompt, answer) lengths: what the work of a
    run is, seed aside."""
    return sorted((int(it.tokens.shape[0]), it.max_new_tokens)
                  for it in items)
