"""Serve full-width Llama-3.2-1B on a TPU once and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the mesh path only

The model is ``llama3_1b`` at its published widths (``configs/llama3_1b.py``:
16 layers, d_model 2048, 32 heads, 8 KV heads, d_ff 8192, vocab 128256) with
random weights from ``jax.random.key(0)``, served through the engine the
serving launcher (``repro.launch.serve --continuous``) builds: paged KV,
fused decode kernel, prefix cache, chunked prefill. One chip runs:

* ``bf16`` — 8 seeded requests (a shared 64-token prefix, prompts of
  128-512 tokens, 32 new tokens each, staggered arrivals) through 8 slots;
  fails unless every layer of the decode program runs the fused kernel;
* ``kernel`` — the fused paged-decode kernel against
  ``paged_decode_attention_ref`` on one decode batch, and the engine's
  greedy tokens on the fused path against the gather path;
* ``plan`` — ``calibrate`` at full width on two seeded 1x256 batches,
  ``bundle.solve(tau=0.005, objective="ET")``, and the same requests served
  under the plan.

``--four-chips`` serves the same requests on one device and under the
``data=1,model=4`` and ``data=2,model=2`` serving meshes, and nothing else.

Each phase prints one JSON line. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase exits non-zero before it. Everything runs in this one
process: a chip belongs to the process that first touches JAX.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.extend  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.pipeline import AMPOptions, calibrate  # noqa: E402
from repro.core.sensitivity import collect_ops  # noqa: E402
from repro.data.synthetic import SyntheticConfig, SyntheticLM  # noqa: E402
from repro.hw.profiles import profile_for_device_kind  # noqa: E402
from repro.kernels.paged_attention import paged_decode_attention  # noqa: E402
from repro.kernels.ref import paged_decode_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import mesh_from_spec  # noqa: E402
from repro.models.registry import get_model  # noqa: E402
from repro.nn.layers import use_fused_paged  # noqa: E402
from repro.quant.qops import QuantContext  # noqa: E402
from repro.serve import ContinuousBatchingEngine, Request  # noqa: E402

ARCH = "llama3_1b"
N_SLOTS = 8
N_REQUESTS = 8
MAX_LEN = 1024            # KV pool: 8 slots x 1024 tokens
BLOCK = 16
CHUNK = 128               # prefill chunk length
PROMPT_LENS = (128, 512)
SHARED_PREFIX = 64        # every prompt opens with the same 64 tokens
NEW_TOKENS = 32
ARRIVAL_EVERY = 2         # engine ticks between arrivals
TAU = 0.005
CALIB_BATCHES, CALIB_LEN = 2, 256
MESH_SPECS = ("data=1,model=4", "data=2,model=2")
# Kernel vs reference: both round scores and probabilities to bf16 and
# accumulate in f32, in different orders, so an element may land one bf16
# ulp (2^-8 relative) apart before the output's own bf16 rounding. 2^-6
# leaves room for that and still fails a layout or masking error, which
# moves outputs by O(1).
KERNEL_TOL = 2.0 ** -6
# Greedy tokens on two numerically different but correct paths agree until
# a near-tie flips one argmax, and the request diverges from there. A wrong
# attention path disagrees from the first decoded token on. Half of all
# tokens separates the two.
AGREEMENT_FLOOR = 0.5


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: {msg}")


def emit(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}), flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's read time counts as compile time)."""

    def __init__(self):
        self.seconds, self.n, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, mark: tuple) -> dict:
        s, n, h = mark
        return {"compile_s": self.seconds - s, "n_compiles": self.n - n,
                "cache_hits": self.cache_hits - h}

    def mark(self) -> tuple:
        return self.seconds, self.n, self.cache_hits


def peak_bytes() -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


def device_check(n_chips: int):
    dev = jax.devices()[0]
    require(dev.platform == "tpu",
            f"needs a TPU; JAX found {dev.platform!r} ({dev.device_kind})")
    require(len(jax.devices()) >= n_chips,
            f"needs {n_chips} chips; JAX found {len(jax.devices())}")
    hw = AMPOptions().hw
    try:
        attached = profile_for_device_kind(dev.device_kind)
    except LookupError as e:
        raise SystemExit(f"chip_smoke: {e}") from None
    require(attached is hw,
            f"AMPOptions.hw is {hw.name} but the attached {dev.device_kind!r} "
            f"is {attached.name}")
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), hw_profile=hw.name)
    return dev


def make_requests(vocab: int, seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, SHARED_PREFIX)
    reqs = []
    for i in range(N_REQUESTS):
        n = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
        tail = rng.integers(0, vocab, n - SHARED_PREFIX)
        reqs.append(Request(rid=i, tokens=np.concatenate([prefix, tail])
                            .astype(np.int32),
                            max_new_tokens=NEW_TOKENS,
                            arrival=i * ARRIVAL_EVERY))
    return reqs


def make_engine(model, **kw) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(model, n_slots=N_SLOTS, max_len=MAX_LEN,
                                    block_size=BLOCK, chunk_len=CHUNK,
                                    prefix_cache=True, **kw)


def checked_tokens(summary, reqs, vocab: int) -> np.ndarray:
    """(n_requests, NEW_TOKENS) greedy tokens; fails unless every request
    finished cleanly with in-vocabulary tokens."""
    faults = summary.counters["faults"]
    require(not faults["seen"] and not faults["degraded_paged_attn"],
            f"drain saw faults: {faults}")
    rows = []
    for r in reqs:
        res = summary.results[r.rid]
        toks = np.asarray(res.tokens)
        require(res.status == "ok" and toks.shape == (r.max_new_tokens,),
                f"request {r.rid}: status {res.status}, {toks.shape} tokens")
        require(bool(np.all((toks >= 0) & (toks < vocab))),
                f"request {r.rid}: token outside the vocabulary")
        rows.append(toks)
    return np.stack(rows)


def agreement(a: np.ndarray, b: np.ndarray) -> dict:
    """Share of equal tokens, of equal first tokens, and the mean share of
    each request's tokens before the first disagreement."""
    same = a == b
    prefix = np.where(same.all(1), a.shape[1], np.argmin(same, 1))
    return {"tokens": float(same.mean()), "first": float(same[:, 0].mean()),
            "prefix": float(prefix.mean() / a.shape[1])}


def _count_primitive(jaxpr, name: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    n += _count_primitive(sub, name)
    return n


def decode_kernel_calls(eng, params) -> int:
    """Pallas kernel calls in the engine's traced decode step: one per
    layer that runs the fused paged kernel. (Lowered text would not do: it
    emits one function for the 16 identical calls.)"""
    pool = eng._pool
    jaxpr = jax.make_jaxpr(eng.decode_step)(
        params, pool.caches, jnp.zeros((eng.n_slots, 1), jnp.int32),
        jnp.zeros((eng.n_slots,), jnp.int32), pool.block_tables_device())
    return _count_primitive(jaxpr.jaxpr, "pallas_call")


def fused_layers(model, plan=None) -> int:
    """Layers whose decode attention should take the fused kernel, by the
    model's own switch: all, unless a plan quantizes a layer's attention
    BGEMMs."""
    ctx = QuantContext(mode="mp", mp=plan.assignment) if plan else \
        QuantContext()
    return sum(use_fused_paged(ctx, f"layers/{i}/attn", "fused")
               for i in range(model.cfg.n_layers))


def serve_drains(eng, params, reqs, clock) -> dict:
    """Three drains of the same requests: the first compiles, the second
    meets the prefix index the first left behind, the third is steady."""
    mark = clock.mark()
    walls, summaries = [], []
    for _ in range(3):
        t = time.perf_counter()
        summaries.append(eng.serve(params, reqs))
        walls.append(time.perf_counter() - t)
    vocab = eng.model.cfg.vocab_size
    first = checked_tokens(summaries[0], reqs, vocab)
    steady = checked_tokens(summaries[2], reqs, vocab)
    c = summaries[2].counters
    return {"tokens": first, **clock.since(mark),
            "first_drain_s": walls[0], "steady_drain_s": walls[2],
            "steady_tokens_per_s": summaries[2].tokens_per_s,
            "steady_decode_steps": summaries[2].n_steps,
            "paged_attn": c["paged_attn"],
            "prefix_hit_tokens_first_drain":
                summaries[0].counters["prefix_hit_tokens"],
            "repeat_agreement": agreement(first, steady)}


def bf16_arm(model, params, reqs, clock) -> np.ndarray:
    eng = make_engine(model)
    r = serve_drains(eng, params, reqs, clock)
    calls = decode_kernel_calls(eng, params)
    tokens = r.pop("tokens")
    emit("bf16", **r, kernel_calls_per_decode_step=calls,
         peak_bytes_in_use=peak_bytes())
    require(r["paged_attn"] == "fused" and calls == model.cfg.n_layers,
            f"decode took the gather path: paged_attn {r['paged_attn']}, "
            f"{calls} kernel calls for {model.cfg.n_layers} layers")
    return tokens


def kernel_check(model, params, reqs, bf16_tokens, clock) -> None:
    mark = clock.mark()
    cfg = model.cfg
    B, Hkv, D = N_SLOTS, cfg.n_kv_heads, cfg.d_head
    G = cfg.n_heads // Hkv
    n_pages = MAX_LEN // BLOCK
    n_blocks = 1 + B * n_pages
    rng = np.random.default_rng(1)
    lengths = rng.integers(1, MAX_LEN + 1, B).astype(np.int32)
    lengths[:2] = MAX_LEN, 1
    tables = np.full((B, n_pages), -1, np.int32)
    ids = iter(rng.permutation(np.arange(1, n_blocks)))
    for b in range(B):
        live = -(-int(lengths[b]) // BLOCK)
        tables[b, :live] = [next(ids) for _ in range(live)]
        stale = rng.random(n_pages - live) < 0.5     # freed slots' leftovers
        tables[b, live:][stale] = rng.integers(1, n_blocks, int(stale.sum()))
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (B, Hkv, G, D), jnp.bfloat16)
    k = jax.random.normal(kk, (n_blocks, Hkv, BLOCK, D), jnp.bfloat16)
    v = jax.random.normal(kv, (n_blocks, Hkv, BLOCK, D), jnp.bfloat16)
    bt, ln = jnp.asarray(tables), jnp.asarray(lengths)
    errs = {}
    for window in (None, 256):
        kw = dict(window=window, scale=math.sqrt(D), scale_mode="div",
                  score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
                  out_dtype=jnp.bfloat16)
        got = jax.jit(lambda *a: paged_decode_attention(*a, **kw))(
            q, k, v, bt, ln)
        want = jax.jit(lambda *a: paged_decode_attention_ref(*a, **kw))(
            q, k, v, bt, ln)
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        require(bool(np.all(np.isfinite(got))), "kernel output not finite")
        errs[str(window)] = float(np.max(np.abs(got - want)))
        require(bool(np.allclose(got, want, rtol=KERNEL_TOL,
                                 atol=KERNEL_TOL)),
                f"fused kernel vs reference (window {window}): max abs "
                f"error {errs[str(window)]:.3g} beyond {KERNEL_TOL:g}")
    eng = make_engine(model, paged_attn="gather")
    summary = eng.serve(params, reqs)
    gather = checked_tokens(summary, reqs, cfg.vocab_size)
    gather_calls = decode_kernel_calls(eng, params)
    agree = agreement(bf16_tokens, gather)
    emit("kernel", max_abs_error=errs, tolerance=KERNEL_TOL,
         fused_vs_gather=agree, gather_paged_attn=summary.counters[
             "paged_attn"], gather_kernel_calls_per_decode_step=gather_calls,
         **clock.since(mark))
    require(gather_calls == 0, f"gather engine made {gather_calls} kernel "
            f"calls per decode step")
    require(agree["first"] == 1.0,
            "first tokens differ, yet prefill is one program on both paths")
    require(agree["tokens"] >= AGREEMENT_FLOOR,
            f"fused and gather agree on {agree['tokens']:.2%} of tokens")


def probe_op_chunk(model, params, batch) -> int:
    """Ops per calibration backward pass, so that one pass's probes take
    about a third of the free device memory (chunks are uneven: the one
    holding the tied lm_head carries a 2.6 GB probe of its own). Each
    operand element of a probed op costs an f32 probe, its f32 gradient and
    a bf16 capture: 10 bytes."""
    ops = collect_ops(lambda p, b, ctx: model.loss(p, b, ctx), params, batch)
    need = sum(10 * (math.prod(o.lhs_shape) + math.prod(o.rhs_shape))
               for o in ops)
    stats = jax.devices()[0].memory_stats()
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    return math.ceil(len(ops) / max(1, math.ceil(need / (free / 3))))


def plan_arm(model, params, reqs, bf16_tokens, clock) -> None:
    data = SyntheticLM(SyntheticConfig(vocab_size=model.cfg.vocab_size,
                                       batch=1, seq_len=CALIB_LEN, seed=0))
    batches = [data.batch_at(i) for i in range(CALIB_BATCHES)]
    op_chunk = probe_op_chunk(model, params, batches[0])
    mark = clock.mark()
    t = time.perf_counter()
    bundle = calibrate(model, params, batches,
                       AMPOptions(tau=TAU, objective="ET"), op_chunk=op_chunk)
    calibrate_s = time.perf_counter() - t
    calib_compile = clock.since(mark)
    plan = bundle.solve(tau=TAU, objective="ET")
    require(plan.predicted_loss_mse <= plan.budget,
            f"plan's predicted loss MSE {plan.predicted_loss_mse:.3e} "
            f"exceeds its budget {plan.budget:.3e}")
    eng = make_engine(model, mp=plan)
    r = serve_drains(eng, params, reqs, clock)
    calls = decode_kernel_calls(eng, params)
    tokens = r.pop("tokens")
    emit("plan", calibrate_s=calibrate_s, calibrate_compile=calib_compile,
         op_chunk=op_chunk, n_ops=len(bundle.sens.ops),
         ops_quantized=plan.n_quantized,
         gain_tier=plan.meta["gain_tier"],
         predicted_loss_mse=plan.predicted_loss_mse, budget=plan.budget,
         predicted_gain=plan.predicted_gain, **r,
         kernel_calls_per_decode_step=calls,
         plan_vs_bf16=agreement(bf16_tokens, tokens),
         peak_bytes_in_use=peak_bytes())
    want = fused_layers(model, plan)
    require(calls == want, f"{calls} fused kernel calls under the plan, "
            f"expected {want}")


def mesh_phase(model, params, reqs, clock) -> None:
    vocab = model.cfg.vocab_size
    mark = clock.mark()
    single = checked_tokens(make_engine(model).serve(params, reqs), reqs,
                            vocab)
    emit("single", **clock.since(mark), peak_bytes_in_use=peak_bytes())
    for spec in MESH_SPECS:
        mark = clock.mark()
        eng = make_engine(model, mesh=mesh_from_spec(spec))
        summary = eng.serve(params, reqs)
        tokens = checked_tokens(summary, reqs, vocab)
        fused_ok = eng.mesh_layout.fused_ok(N_SLOTS, model.cfg.n_kv_heads)
        calls = decode_kernel_calls(eng, params)
        agree = agreement(single, tokens)
        emit("mesh", spec=spec, fused_ok=fused_ok,
             kernel_calls_per_decode_step=calls,
             paged_attn=summary.counters["paged_attn"],
             mesh_vs_single=agree, **clock.since(mark),
             peak_bytes_in_use=peak_bytes())
        require(fused_ok and calls == model.cfg.n_layers,
                f"{spec}: fused_ok {fused_ok}, {calls} kernel calls for "
                f"{model.cfg.n_layers} layers")
        require(agree["tokens"] >= AGREEMENT_FLOOR,
                f"{spec}: mesh and single agree on {agree['tokens']:.2%}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path (single device vs "
                         f"{', '.join(MESH_SPECS)}) on four chips")
    args = ap.parse_args()
    dev = device_check(4 if args.four_chips else 1)
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    model = get_model(ARCH)
    params = model.init(jax.random.key(0))
    reqs = make_requests(model.cfg.vocab_size)
    emit("setup", arch=ARCH, n_params=model.n_params(), compile_cache=cache_dir,
         prompt_lens=[r.prompt_len for r in reqs])
    if args.four_chips:
        mesh_phase(model, params, reqs, clock)
    else:
        bf16 = bf16_arm(model, params, reqs, clock)
        kernel_check(model, params, reqs, bf16, clock)
        plan_arm(model, params, reqs, bf16, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
