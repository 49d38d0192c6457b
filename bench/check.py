"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished,
drawn from the seed and holding the longest of them, is replayed through
the plain float32 reference: once over each prompt with its served tokens.
For every served token the reference gives the gap by which that token's
logit lies below the reference's best logit at its position. Served tokens
are greedy, so a correct program loses to the reference only where
rounding flips a near tie: most gaps are 0, the rest small. The number
compared is the mean gap over every served token of the sample; the widest
gap is reported beside it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the control: the configuration's precision one step down, on the
# program's own mixed-precision path
STEP_DOWN = {"bf16": "fp8_e4m3", "fp8_e4m3": "fp4_e2m1"}


def finished(results: dict, items: list) -> list:
    """(rid, prompt, served tokens) of every request the engine finished:
    status ok, every token it was asked for."""
    return [(it.rid, it.tokens, np.asarray(results[it.rid].tokens))
            for it in items if it.rid in results
            and results[it.rid].status == "ok"
            and len(results[it.rid].tokens) == it.max_new_tokens]


def sample(finished: list, rng, tokens: int, max_requests: int) -> list:
    """``finished``: (rid, prompt, served tokens). The longest request
    (prompt plus answer) first, then others in an order drawn from ``rng``,
    until ``tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i][1]) + len(finished[i][2]),
                                 finished[i][0]))
    rest = [i for i in range(len(finished)) if i != longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(finished[i])
        n += len(finished[i][2])
    return out


def _sequence(prompt, served, rows_pad: int) -> tuple:
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    n = len(served)
    if n > rows_pad:
        raise ValueError(f"{n} served tokens exceed {rows_pad}")
    rows = np.zeros((rows_pad,), np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return ids, rows, n


def gaps_of(ref_logits, tokens: np.ndarray) -> np.ndarray:
    """Per row: the reference's best logit minus the logit of ``tokens``."""
    lg = jnp.asarray(ref_logits)[:len(tokens)]
    tok = jnp.asarray(tokens, jnp.int32)[:, None]
    got = jnp.take_along_axis(lg, tok, axis=1)[:, 0]
    return np.asarray(jnp.max(lg, axis=1) - got)


def reference_gaps(ref_fn, sample_: list, rows_pad: int) -> np.ndarray:
    """Every served token's gap over the sample. ``ref_fn(ids, rows)`` ->
    float32 logits at ``rows``."""
    out = [np.zeros((0,), np.float32)]
    for _, prompt, served in sample_:
        ids, rows, n = _sequence(prompt, served, rows_pad)
        out.append(gaps_of(ref_fn(ids, rows), np.asarray(served)))
    return np.concatenate(out)


def control_gaps(ref_fn, first_fn, sample_: list, rows_pad: int):
    """The control's gaps: at each position of the same prompts and served
    tokens, the gap of the token that the lower-precision forward
    ``first_fn(ids, rows)`` puts first."""
    out = [np.zeros((0,), np.float32)]
    for _, prompt, served in sample_:
        ids, rows, n = _sequence(prompt, served, rows_pad)
        chosen = np.asarray(first_fn(ids, rows))[:n]
        out.append(gaps_of(ref_fn(ids, rows), chosen))
    return np.concatenate(out)


def summary(gaps: np.ndarray) -> dict:
    """The numbers a run reports of its gaps. ``mean_logit_gap`` is the one
    compared: see PERF.md for why the widest gap is not."""
    if not len(gaps):
        return {"mean_logit_gap": None, "max_logit_gap": None,
                "off_argmax_share": None, "tokens": 0}
    return {"mean_logit_gap": float(gaps.mean()),
            "max_logit_gap": float(gaps.max()),
            "off_argmax_share": float((gaps > 0).mean()),
            "tokens": int(len(gaps))}
def control_assignment(names, stated: dict, keep_plan: bool = False) -> dict:
    """Each op's precision in the control: one step below the one the
    configuration states (``stated``, a plan's assignment; an op it leaves
    out is bf16). With ``keep_plan`` only the ops in bf16 step down and the
    plan's fp8_e4m3 ops stay: the plan with every op in fp8."""
    def down(name):
        p = stated.get(name, "bf16")
        return p if keep_plan and p != "bf16" else STEP_DOWN[p]
    return {n: down(n) for n in names}


def control_first(server, keep_plan: bool = False):
    """(ids, rows) -> the token the control puts first at each row: the
    served model's own forward pass, teacher-forced over the whole
    sequence, with the precisions of ``control_assignment``."""
    from repro.core.sensitivity import collect_ops
    from repro.quant.qops import QuantContext
    model = server.model
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "labels": jnp.zeros((1, 16), jnp.int32)}
    names = [o.name for o in collect_ops(
        lambda p, b, ctx: model.loss(p, b, ctx), server.params, batch)]
    stated = server.plan.assignment if server.plan is not None else {}
    mp = control_assignment(names, stated, keep_plan)
    ctx = QuantContext(mode="mp", mp=mp, act_scale_token=True)
    t_pad = server.mix["server"]["max_len"]

    @jax.jit
    def first(params, ids, rows):
        logits = model.apply(params, ids[None], ctx)[0]
        return jnp.argmax(logits[rows], axis=-1)

    def fn(ids, rows):
        padded = np.zeros((t_pad,), np.int32)
        padded[:len(ids)] = ids
        return first(server.params, jnp.asarray(padded), jnp.asarray(rows))
    return fn
