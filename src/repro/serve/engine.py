"""Serving engines: one-shot batch serving and continuous batching.

TTFT (the paper's measured quantity, Sec. 2.3.1) = wall time of the compiled
prefill step. Both engines accept ``mp`` as an op->format dict *or* an
``MPPlan`` straight from ``core.pipeline.auto_mixed_precision``, so an
IP-solver artifact is directly servable.

* :class:`ServeEngine` — the paper-measurement harness: one batch in, greedy
  decode to completion, report TTFT + decode throughput.
* :class:`ContinuousBatchingEngine` — production-shaped serving: a request
  queue drains through a fixed pool of cache slots; requests are admitted
  *mid-decode* as slots free up (scheduler), each prefilled request's cache
  is scattered into its slot (cache pool), and one compiled decode step
  advances every occupied slot at its own sequence depth (per-slot position
  vectors). Greedy tokens are identical to the one-shot path — batching is
  across independent cache rows, never across a sequence's own math.

Continuous serving defaults to the **paged** KV layout (``paged=True``):
attention caches are block-major (``PagedCachePool``), admission is
block-budget-aware (a request only enters when its worst-case block need is
coverable — otherwise it queues, the backpressure path), and the compiled
decode step takes the per-slot block tables. ``paged=False`` keeps the dense
per-slot rings for comparison. Paged decode attention defaults to the
**fused** Pallas kernel (``paged_attn="fused"``): block-table indirection is
resolved in-kernel and each step reads only live KV blocks (fp8 caches
dequantized in-register), instead of the ``paged_attn="gather"`` reference
path that materializes the full ``(B, max_blocks * block_size)`` K/V per
layer. Token parity with the dense/one-shot path is exact either way: the
fused kernel reproduces the reference softmax numerics (two-phase, final
max/denominator), the paged gather reproduces the dense key layout in
logical order, and the causal mask / length masking hides everything else.

Prefill is **length-bucketed** in both engines: prompts are padded to a
power-of-two bucket with masked attention/state updates, so admission
compiles O(#buckets) programs instead of O(#distinct prompt lengths). In
paged mode it is additionally **chunked** (``chunk_len``): a prompt longer
than the chunk budget is split into fixed-size chunks written straight into
the slot's paged blocks ("paged prefill" — no dense-then-scatter), each
chunk interleaved with decode steps under a TTFT-aware arbitration budget
(``chunk_budget`` chunk steps per decode step at most), so a long prompt
consumes bounded per-step latency and never head-of-line-blocks decoding
slots. Greedy tokens stay bit-identical to the one-shot engine for prompts
whose bucket stays below ``flash_min_seq``: the serving quant policy uses
per-token activation scales and prefill attends through the KV-cache
storage dtype, making the math invariant to batching, padding and chunk
splits. (At or past ``flash_min_seq`` the one-shot engine takes the
blocked flash kernel, whose summation order differs from the reference
path the chunked step always uses — see the serve README.)
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mpconfig import as_assignment
from repro.kernels.paged_attention import kernel_steps
from repro.launch.steps import (get_serving_step, greedy_next_token,
                                merge_first_tokens, nonfinite_rows,
                                shadow_logit_mse)
from repro.serve.cache_pool import (CachePool, PagedCachePool,
                                    PoolExhausted, dense_slot_bytes,
                                    page_widths, paged_block_bytes,
                                    paged_slot_bytes)
from repro.serve.faults import InjectedFault, poison_logit_rows
from repro.serve.scheduler import (DONE, PREFILLING, RUNNING, WAITING,
                                   Request, Scheduler)
from repro.serve.telemetry import NOSPAN, LiveCounters


class _ImpossibleRequest(Exception):
    """Raised by the paged admission gate when a request's worst-case block
    need exceeds what the pool can ever satisfy. The engine decides whether
    that is a configuration error (pristine pool: fail fast with ValueError,
    as before) or graceful degradation (quarantine shrank capacity under a
    request that used to fit: retire it as ``failed``)."""

    def __init__(self, st, need: int):
        super().__init__(need)
        self.st = st
        self.need = need

__all__ = ["ServeEngine", "ContinuousBatchingEngine", "GenResult",
           "ServeSummary", "prefill_bucket"]


def prefill_bucket(n: int, chunk_len: Optional[int] = None,
                   min_bucket: int = 8) -> int:
    """Padded length for a prefill chunk of ``n`` real tokens: the next
    power of two (>= ``min_bucket``), clamped to ``chunk_len`` when chunking
    is on. Admission compiles one prefill program per bucket instead of one
    per distinct prompt length."""
    assert n >= 1, n
    b = max(min_bucket, 1 << (n - 1).bit_length())
    if chunk_len is not None:
        assert n <= chunk_len, (n, chunk_len)
        b = min(b, chunk_len)
    return b


@dataclasses.dataclass
class GenResult:
    tokens: jax.Array
    ttft_s: float
    decode_s: float
    tokens_per_s: float


@dataclasses.dataclass
class ServeSummary:
    """Outcome of draining a request queue through the continuous engine.

    ``counters`` carries the occupancy/backpressure signals a future
    SLA-aware re-solve hook needs (ROADMAP): peak queue depth, blocked
    admissions, peak live tokens, and — under paging — block occupancy and
    the KV HBM actually pinned (``peak_kv_bytes``) vs the dense-slot cost
    (``dense_kv_bytes``).
    """
    results: dict                     # rid -> RequestResult
    n_steps: int                      # decode steps executed
    decode_s: float                   # wall time inside decode steps
    total_s: float                    # wall time of the whole drain
    tokens_per_s: float               # decode-produced tokens / decode_s
    counters: dict = dataclasses.field(default_factory=dict)

    def tokens_for(self, rid: int) -> np.ndarray:
        return self.results[rid].tokens


class ServeEngine:
    """One-shot batch serving: prefill + lock-step greedy decode.

    Prefill is length-bucketed for decoder-only LMs on plain token prompts:
    the prompt is padded to a power-of-two bucket and masked, so the compile
    cache is keyed by bucket (the same bucketed step the continuous engine
    uses in dense mode) instead of by distinct prompt length. Multimodal
    prefixes and encoder-decoder models keep the legacy per-length step.
    """

    def __init__(self, model, mp=None, mesh=None, donate: bool = True):
        self.model = model
        self.mp = as_assignment(mp)
        self.mesh = mesh
        self.prefill_step = get_serving_step(model, "prefill", mp=self.mp,
                                             donate=donate)
        self.decode_step = get_serving_step(model, "decode", mp=self.mp,
                                            donate=donate)
        self._bucketed = getattr(model, "supports_prefill_chunk", False)
        if self._bucketed:
            self.bucketed_prefill_step = get_serving_step(
                model, "bucketed_prefill", mp=self.mp, donate=donate)
        # compile-economy bookkeeping: which prefill programs this engine
        # needed vs how many distinct prompt lengths it served
        self.prefill_compile_keys: set = set()
        self.prompt_lens_seen: set = set()

    # ------------------------------------------------------------------
    def init_caches(self, batch: int, max_len: int, enc_len: int = 0):
        # explicit capability check: enc-dec models declare that their cache
        # needs the encoder length (for pre-computed cross-attention K/V)
        # instead of the engine relying on call-arity coincidence
        if getattr(self.model, "cache_needs_enc_len", False):
            return self.model.init_cache(batch, max_len, enc_len)
        return self.model.init_cache(batch, max_len)

    def _prefill(self, params, caches, batch: dict):
        """Dispatch prefill: bucketed (compiled per power-of-two bucket) when
        the model supports it and the batch is plain tokens; the legacy
        per-length step otherwise. Returns (last-token logits, caches)."""
        tokens = batch["tokens"]
        B, T0 = tokens.shape
        self.prompt_lens_seen.add(int(T0))
        Lb = prefill_bucket(T0)
        # legacy per-length step for multimodal/enc-dec batches, and for
        # prompts whose *bucket* reaches flash_min_seq: the bucketed step
        # never flashes (padding must not change the summation order), so
        # long prompts keep the flash-capable pre-bucketing path — and its
        # exact pre-bucketing numerics — at per-length compile cost
        if (not self._bucketed or "frames" in batch
                or batch.get("prefix_embeds") is not None
                or Lb >= getattr(self.model.cfg, "flash_min_seq", 1 << 30)):
            self.prefill_compile_keys.add(("legacy", int(T0)))
            return self.prefill_step(params, caches, batch)
        self.prefill_compile_keys.add(Lb)
        tok = jnp.pad(jnp.asarray(tokens, jnp.int32),
                      ((0, 0), (0, Lb - T0)))
        start = jnp.zeros((B,), jnp.int32)
        valid = jnp.full((B,), T0, jnp.int32)
        return self.bucketed_prefill_step(params, caches, tok, start, valid)

    def ttft(self, params, batch: dict, max_len: int, n_iters: int = 5,
             n_warmup: int = 2) -> float:
        """Median prefill wall time (the paper averages 5 iterations).

        Measures the *serving* prefill path: short prompts run the bucketed
        step, so the cost includes pow-2 bucket padding (that is what a
        deployment executes); prompts at or beyond flash_min_seq run the
        legacy unpadded flash-capable step, keeping long-context TTFT
        comparable with pre-bucketing measurements."""
        B = batch["tokens"].shape[0]
        enc_len = batch["frames"].shape[1] if "frames" in batch else 0
        times = []
        for i in range(n_warmup + n_iters):
            caches = self.init_caches(B, max_len, enc_len)
            t0 = time.perf_counter()
            logits, caches = self._prefill(params, caches, batch)
            jax.block_until_ready(logits)
            if i >= n_warmup:
                times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    # ------------------------------------------------------------------
    def generate(self, params, batch: dict, max_new_tokens: int,
                 max_len: Optional[int] = None) -> GenResult:
        tokens = batch["tokens"]
        B, T0 = tokens.shape
        enc_len = batch["frames"].shape[1] if "frames" in batch else 0
        prefix = 0
        if batch.get("prefix_embeds") is not None:
            prefix = batch["prefix_embeds"].shape[1]
        max_len = max_len or (T0 + prefix + max_new_tokens)
        caches = self.init_caches(B, max_len, enc_len)

        t0 = time.perf_counter()
        logits, caches = self._prefill(params, caches, batch)
        jax.block_until_ready(logits)
        ttft = time.perf_counter() - t0

        out = [jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)]
        t1 = time.perf_counter()
        pos = T0 + prefix
        for i in range(max_new_tokens - 1):
            logits, caches = self.decode_step(
                params, caches, out[-1][:, None], jnp.array(pos + i, jnp.int32))
            out.append(jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32))
        jax.block_until_ready(out[-1])
        dt = time.perf_counter() - t1
        toks = jnp.stack(out, axis=1)
        return GenResult(tokens=toks, ttft_s=ttft, decode_s=dt,
                         tokens_per_s=B * max_new_tokens / max(dt, 1e-9))


class ContinuousBatchingEngine:
    """Continuous batching over a fixed pool of cache slots.

    The drain loop alternates two phases per clock tick:

    1. *admission* — while a slot is free and the FCFS queue head has
       arrived, prefill it (batch=1), scatter its cache into the slot, and
       record its first greedy token + TTFT;
    2. *decode* — one compiled step over all ``n_slots`` rows with per-slot
       ``(B,)`` position and token vectors; finished requests release their
       slot, which the next tick's admission phase can immediately reuse.

    Vacant slots decode garbage rows; their outputs are ignored and their
    cache rows (dense) are fully reset at the next first-chunk prefill — or
    their writes land in the paged pool's trash block — so they cost FLOPs
    but never correctness.

    Prefill runs *in place* on the pool's caches with the decode batch
    width: each prefill-chunk step carries (tokens, start, valid) vectors
    over all ``n_slots`` rows, co-batching every prefilling slot whose next
    chunk shares a bucket while decoding/vacant rows pass through untouched
    (valid = 0). Paged mode writes the chunk straight into the slot's
    physical blocks (allocated incrementally per chunk); dense mode buckets
    whole prompts into the slot's ring. Compile cost is O(#buckets).

    ``chunk_len`` (paged only) splits prompts longer than the budget into
    fixed-size chunks; the step loop then interleaves at most
    ``chunk_budget`` chunk steps per decode step, so no decoding slot ever
    waits more than ``chunk_budget`` steps while a long prompt prefills
    (``ServeSummary.counters``: ``prefill_chunks``, ``decode_stall_steps``,
    ``max_decode_stall_run``, stall percentiles).

    ``paged_attn`` (paged only) selects the decode-attention implementation:
    ``"fused"`` (default) runs the Pallas paged-attention kernel directly
    over the block-major cache; ``"gather"`` keeps the reference
    gather-then-attend path. Greedy tokens are identical; the counters
    ``decode_attn_bytes_{read,fused_model,gather_model}`` expose the
    live-vs-capacity HBM-read gap between the two.

    ``prefix_cache`` (paged only; auto-on for pure-attention archs) shares
    KV blocks across requests: admission content-hashes the prompt block by
    block against a resident prefix index (chained digests — a match
    implies the whole prefix matches), maps matched blocks into the slot's
    table with a refcount bump, and starts prefill at the first unmatched
    token. Blocks are copy-on-write: a shared page in a chunk's write range
    is forked (device-side block copy) before the write, so a parent chain
    is never mutated. Freed refcount-0 indexed blocks park in a per-shard
    LRU and are reclaimed only under allocation pressure. Because the
    serving quant policy makes each token's K/V a pure function of the
    tokens at or before it, a cache hit is bit-exact: greedy tokens with
    sharing on equal sharing off.

    ``adaptive`` (an :class:`~repro.serve.adaptive.AdaptiveMPController`)
    closes the solver<->scheduler loop: once per tick, at the step boundary
    before admission, the engine feeds the controller its live counters
    (queue depth, cumulative blocked admissions, KV occupancy, decode-stall
    p99) and — when the controller's hysteresis says so — swaps every
    serving step to the plan for the new tau level via the
    ``get_serving_step`` memo (the MP assignment is part of the memo key:
    a swap is a dispatch switch, not a recompile) and invalidates the
    prefix index (quantized K/V bytes are plan-dependent). With no
    controller, or one that never fires, greedy tokens are bit-identical
    to a plain fixed-plan engine. ``ServeSummary.counters["adaptive"]``
    records the downshift/restore tallies and every swap's step/tau.

    ``chunk_len`` in *dense* mode switches prefill to the ring-aware
    chunked step over rings widened by ``chunk_len``
    (``init_cache(chunk_extra=...)``): a windowed ring sized exactly
    ``window`` truncates a chunk that straddles the window boundary when
    ``window`` is not chunk-aligned, so the widened ring keeps the current
    chunk plus a full window of context resident.

    ``preemption`` (paged only): when admission is gated on resources and
    the best arrived waiter has strictly higher ``Request.priority`` than a
    live request, the lowest-priority/latest-admitted slot is evicted back
    to the waiting queue (blocks freed — its prefix stays cached, so
    resume re-prefills nearly for free) and retried on the same tick. A
    resumed request re-prefills prompt + generated-so-far and continues;
    its tokens are identical to an uninterrupted run. At uniform priority
    nothing is ever evicted (pure FCFS backpressure, as before).

    ``telemetry`` (a :class:`~repro.serve.telemetry.Telemetry`) records
    each drain's host-loop spans — ticks, admission, input building, step
    calls and waits on delivery — in memory and as profiler annotations;
    with None (the default) no span is made. The counts of the drain in
    progress (``live_counters``) are kept either way, and
    ``ServeSummary.counters`` is built from them.
    """

    def __init__(self, model, n_slots: int = 4, max_len: int = 512,
                 mp=None, donate: bool = False, paged: bool = True,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 chunk_len: Optional[int] = None, chunk_budget: int = 1,
                 min_bucket: int = 8, paged_attn: Optional[str] = None,
                 mesh=None, prefix_cache: Optional[bool] = None,
                 preemption: bool = True, prefill_cobatch: bool = True,
                 adaptive=None, faults=None, max_retries: int = 1,
                 guardrail=None, kernel_fault_limit: int = 2,
                 telemetry=None):
        if getattr(model, "cache_needs_enc_len", False):
            raise NotImplementedError(
                "continuous batching currently serves decoder-only LMs")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        # load-adaptive MP: the controller owns the plan ladder; the engine
        # consults it once per tick (step boundary) and swaps the serving
        # steps through the get_serving_step memo on its say-so
        self.adaptive = adaptive
        if adaptive is not None:
            if mp is not None:
                raise ValueError(
                    "pass the base plan through the controller (its level-0 "
                    "tau), not both mp= and adaptive=")
            mp = adaptive.plan
        self.mp = as_assignment(mp)
        # the plan *object* (not just the assignment): the tau-anchored
        # guardrail reads its solved loss-MSE budget (tau^2 E[g^2])
        self._mp_plan = mp
        # fault tolerance: injector hooks (tests/CI), bounded per-request
        # retry budget through the resume machinery, the tau-anchored
        # numerical guardrail, and the kernel-fault count past which fused
        # paged attention degrades to the gather reference path
        self.faults = faults
        self.max_retries = int(max_retries)
        self.guardrail = guardrail
        self.kernel_fault_limit = int(kernel_fault_limit)
        assert self.max_retries >= 0, max_retries
        assert self.kernel_fault_limit >= 1, kernel_fault_limit
        if not paged and n_blocks is not None:
            raise ValueError("n_blocks only applies to paged mode; drop it "
                             "or remove paged=False")
        if paged_attn is not None and not paged:
            raise ValueError("paged_attn selects the paged decode-attention "
                             "implementation; drop it or remove paged=False")
        if paged_attn is None:
            paged_attn = "fused"
        if paged_attn not in ("fused", "gather"):
            raise ValueError(f"paged_attn must be 'fused' or 'gather', got "
                             f"{paged_attn!r}")
        self.paged_attn = paged_attn
        if chunk_len is not None:
            assert chunk_len >= 1, chunk_len
            ssm = getattr(model.cfg, "ssm", None)
            if ssm is not None and chunk_len % ssm.chunk != 0:
                raise ValueError(
                    f"chunk_len {chunk_len} must be a multiple of the SSD "
                    f"chunk ({ssm.chunk}): engine chunk boundaries must "
                    f"align with the SSD state recurrence for bit-exact "
                    f"resume (override cfg.ssm.chunk or pick another "
                    f"chunk_len)")
        assert chunk_budget >= 1, chunk_budget
        self.paged = paged
        self.block_size = block_size
        self.n_blocks = n_blocks
        self.chunk_len = chunk_len
        self.chunk_budget = chunk_budget
        self.min_bucket = min_bucket
        # cross-request prefix caching: content-hash admitted prompts
        # against resident KV blocks and skip prefill for matched prefixes.
        # Auto-on for paged pure-attention archs; SSM/hybrid archs carry
        # slot-major state that a cached KV chain cannot reconstruct, so
        # they auto-disable (and asking explicitly is an error)
        if prefix_cache and not paged:
            raise ValueError("prefix_cache shares paged KV blocks; drop it "
                             "or remove paged=False")
        if paged:
            ssm_bytes = paged_slot_bytes(model, block_size)
            if prefix_cache and ssm_bytes > 0:
                raise ValueError(
                    "prefix_cache is unavailable for SSM/hybrid archs: "
                    "slot-major SSM state is not reconstructible from "
                    "shared KV blocks, so a matched prefix could not skip "
                    "prefill")
            if prefix_cache is None:
                prefix_cache = ssm_bytes == 0
        self.prefix_cache = bool(prefix_cache) and paged
        # preemption: under block pressure a strictly higher-priority
        # waiter evicts the lowest-priority live slot (paged only — resume
        # re-prefills the effective prompt, nearly free when its prefix is
        # still cached). At uniform priority nothing is ever evicted.
        self.preemption = bool(preemption) and paged
        # co-batch prefill chunks across buckets: pad every prefilling
        # slot's next chunk to the largest bucket and run ONE chunk step,
        # instead of one step per bucket group (padding is masked per row,
        # so numerics are unchanged)
        self.prefill_cobatch = bool(prefill_cobatch)
        # mesh-sharded serving: plan the layout once (pool geometry + page
        # sharding), compile mesh-aware steps, and resolve n_blocks so the
        # host allocator and the device layout agree
        self.mesh = mesh
        from repro.serve.parallel import make_serving_layout
        self.mesh_layout = make_serving_layout(
            mesh, n_slots=n_slots, max_len=max_len, block_size=block_size,
            n_blocks=n_blocks, paged=paged)
        if self.mesh_layout is not None and paged:
            self.n_blocks = self.mesh_layout.n_blocks
        # dense mode with chunk_len uses the ring-aware chunked step over
        # rings widened by chunk_len (chunk_extra), so a chunk straddling a
        # window boundary is never truncated
        self._prefill_kind = ("chunked_prefill" if paged else
                              ("dense_chunked_prefill" if chunk_len is not None
                               else "bucketed_prefill"))
        self._donate = donate
        self.prefill_chunk_step = get_serving_step(
            model, self._prefill_kind,
            mp=self.mp, mesh_layout=self.mesh_layout)
        self.decode_step = get_serving_step(
            model, "paged_decode" if paged else "decode", mp=self.mp,
            paged_attn=paged_attn if paged else None, donate=donate,
            mesh_layout=self.mesh_layout)
        # one pool per engine, persisted across serve() drains (the paged
        # prefix index survives between calls); built lazily by _make_pool
        self._pool = None
        # compile-economy bookkeeping (persists across serve() calls, like
        # the jit compile cache it mirrors)
        self.prefill_compile_keys: set = set()
        self.prompt_lens_seen: set = set()
        self._warned_flash = False
        # external control plane: cancel()/shutdown() may be called from any
        # thread (e.g. an on_token callback); the drain loop applies pending
        # control at the top of each tick, so cancellation is race-free with
        # respect to slot reuse
        self._ctl_lock = threading.Lock()
        self._cancel_pending: set = set()
        self._shutdown_flag = False
        # spans of every drain (a repro.serve.telemetry.Telemetry), or None;
        # the counters of the drain in progress (the last one after it ends)
        # are kept either way
        self.telemetry = telemetry
        self.live_counters = None

    # ------------------------------------------------------------------
    def cancel(self, rid: int) -> None:
        """Request cancellation of ``rid``. Thread-safe; takes effect at the
        next tick. The request retires with ``status="cancelled"`` keeping
        whatever tokens it had committed by then (possibly none)."""
        with self._ctl_lock:
            self._cancel_pending.add(rid)

    def shutdown(self) -> None:
        """Ask the current ``serve()`` drain to stop: every unfinished
        request is cancelled at the next tick, in-flight token transfers are
        drained, and ``serve()`` returns normally with partial results."""
        with self._ctl_lock:
            self._shutdown_flag = True

    # ------------------------------------------------------------------
    def _make_pool(self):
        """The engine's one pool, persisted across ``serve()`` drains so
        the paged prefix index (and its cached blocks) carries over: a
        prompt prefix indexed by one drain is a bit-exact cache hit in the
        next. Rebuilt only when a previous drain leaked slots (it errored
        mid-flight) — a clean drain frees every slot on the way out."""
        pool = self._pool
        if pool is not None and pool.n_free_slots == self.n_slots:
            pool.reset_counters()
            return pool
        if self.paged:
            pool = PagedCachePool(self.model, self.n_slots, self.max_len,
                                  block_size=self.block_size,
                                  n_blocks=self.n_blocks,
                                  mesh_layout=self.mesh_layout)
        else:
            pool = CachePool(self.model, self.n_slots, self.max_len,
                             mesh_layout=self.mesh_layout,
                             chunk_extra=self.chunk_len or 0)
        self._pool = pool
        return pool

    def _swap_plan(self, plan) -> None:
        """Apply a new MP plan at a step boundary: repoint the serving
        steps at the new assignment through the ``get_serving_step`` memo
        (the plan is part of the memo key, so a previously-seen plan is a
        dispatch switch, not a recompile) and invalidate the prefix index —
        quantized K/V bytes are plan-dependent, so blocks written under the
        old plan must not satisfy hits under the new one."""
        self.mp = as_assignment(plan)
        self._mp_plan = plan
        self.prefill_chunk_step = get_serving_step(
            self.model, self._prefill_kind, mp=self.mp,
            mesh_layout=self.mesh_layout)
        self.decode_step = get_serving_step(
            self.model, "paged_decode" if self.paged else "decode",
            mp=self.mp, paged_attn=self.paged_attn if self.paged else None,
            donate=self._donate, mesh_layout=self.mesh_layout)
        if self._pool is not None:
            self._pool.invalidate_prefix_index()

    def _digests(self, pool, st):
        """Chained prefix digests of the request's *effective* prompt
        (recomputed after a preemption — the generated tokens extend the
        chain, so a resumed request matches its own still-cached blocks)."""
        if not self.prefix_cache:
            return None
        if st.digests is None:
            st.digests = pool.prefix_digests(st.effective_tokens)
        return st.digests

    def _admit(self, params, pool, sched: Scheduler, now: int,
               evict=None, on_impossible=None) -> int:
        """Claim slots for admissible requests and emit prefill work items;
        no device work happens here — the step loop drives the chunks.
        Returns the number of requests admitted.

        ``evict`` (paged + preemption) is the engine's eviction hook: when
        the best arrived waiter is gated on resources and outranks a live
        request, the scheduler's victim is evicted (freeing its slot +
        blocks; its prefix blocks stay cached) and admission retries —
        bounded by the live-slot count, since every round removes one
        victim and equal priority never preempts.

        ``on_impossible`` handles a request whose worst-case block need no
        pool state can ever cover: when block quarantine shrank capacity
        under a request that fit the pristine pool, the serve loop retires
        it as ``failed`` instead of crashing the drain; a request that
        never fit stays the fail-fast ValueError it always was."""
        gate = None
        if self.paged:
            def gate(r):
                st = sched.states[r.rid]
                plen = st.effective_prompt_len
                mnew = st.remaining_new_tokens
                need = pool.blocks_for_request(plen, mnew)
                if need > pool.allocatable_blocks:
                    # would block the queue forever — surface it instead
                    raise _ImpossibleRequest(st, need)
                return pool.can_admit(plen, mnew,
                                      digests=self._digests(pool, st))
        admitted = 0
        while True:
            while pool.n_free_slots:
                try:
                    st = sched.pop_admissible(now, gate)
                except _ImpossibleRequest as exc:
                    if (on_impossible is not None
                            and pool.n_quarantined_blocks > 0
                            and exc.need <= pool.blocks_per_shard - 1):
                        on_impossible(exc.st)
                        continue
                    raise ValueError(
                        f"request {exc.st.request.rid} needs {exc.need} KV "
                        f"blocks but the pool has only "
                        f"{pool.allocatable_blocks}; raise --n-blocks or "
                        f"shrink the request") from None
                if st is None:
                    break
                req = st.request
                assert req.prompt_len + req.max_new_tokens <= self.max_len, (
                    f"request {req.rid}: {req.prompt_len}+"
                    f"{req.max_new_tokens} exceeds pool max_len "
                    f"{self.max_len}")
                self.prompt_lens_seen.add(req.prompt_len)
                # documented parity boundary, enforced with a one-time
                # warning: the chunked/bucketed step never flashes, so once
                # a chunk bucket reaches flash_min_seq, greedy tokens may
                # differ from a flash-capable one-shot reference in
                # low-order summation bits
                flash_min = getattr(self.model.cfg, "flash_min_seq", 1 << 30)
                biggest = min(req.prompt_len,
                              self.chunk_len or req.prompt_len)
                if (not self._warned_flash
                        and prefill_bucket(biggest, self.chunk_len,
                                           self.min_bucket) >= flash_min):
                    self._warned_flash = True
                    print(f"[serve] warning: prefill bucket >= "
                          f"flash_min_seq ({flash_min}); chunked prefill "
                          f"uses the reference attention path, so "
                          f"bit-parity with a flash one-shot reference is "
                          f"not guaranteed at these lengths")
                start_at = 0
                if self.paged:
                    # reservation only — blocks materialize chunk by chunk;
                    # matched prefix blocks are mapped in and skipped
                    slot = pool.alloc_slot(st.effective_prompt_len,
                                           st.remaining_new_tokens,
                                           digests=self._digests(pool, st))
                    start_at = pool.matched_tokens(slot)
                else:
                    slot = pool.alloc()
                sched.start_prefill(st, slot, now, start_at=start_at)
                admitted += 1
                if st.wall_admitted == 0.0:   # resumed: keep first admission
                    st.wall_admitted = time.perf_counter()
            if evict is None:
                return admitted
            cand = sched.peek_admissible(now)
            if cand is None:
                return admitted
            victim = sched.preempt_candidate(cand.request.priority)
            if victim is None:
                return admitted
            if not evict(victim):
                return admitted

    def _prefill_rows(self, pool, sched: Scheduler):
        """Host-side inputs of the next prefill-chunk step: co-batch the
        next chunk of every prefilling slot — across buckets, padded to the
        largest one (``prefill_cobatch``), or the legacy same-bucket-as-head
        group — over the full ``n_slots`` batch (inactive rows pass through
        with valid = 0). Chunk order is priority, then shortest remaining
        prefill.

        Returns ``(items, bucket, tok, start, valid, alloc_failed)``: the
        ``(slot, state, start, take)`` chunks of the step, its bucket width,
        the (n_slots, bucket) token rows and the per-row start/valid
        vectors, and the states whose page allocation failed (dropped from
        the step; the caller contains them). ``items`` is empty when every
        candidate's allocation failed."""
        cands = []
        for slot, st in sched.prefilling.items():
            start = st.prefill_pos
            take = st.effective_prompt_len - start
            if self.chunk_len is not None:
                take = min(take, self.chunk_len)
            cands.append((slot, st, start, take))
        # priority classes first, then shortest-remaining-prefill-first:
        # the prompt closest to producing its first token (and freeing
        # chunk bandwidth) goes first — with prefix caching, a mostly
        # cached prompt has a tiny remainder and jumps the line
        cands.sort(key=lambda c: (-c[1].request.priority,
                                  c[1].effective_prompt_len - c[1].prefill_pos,
                                  c[0]))
        # materialize each candidate's pages first (a borrowed page in the
        # write range is COW-forked here): a per-slot allocation failure —
        # injected, or organic under quarantine pressure — drops only that
        # slot from the step, never the whole tick
        alloc_failed = []
        if self.paged:
            ok = []
            for slot, st, start, take in cands:
                try:
                    if self.faults is not None:
                        self.faults.on_alloc(slot)
                    pool.ensure_range(slot, start, start + take)
                except (InjectedFault, PoolExhausted):
                    alloc_failed.append(st)
                    continue
                ok.append((slot, st, start, take))
            cands = ok
            if not cands:
                return [], None, None, None, None, alloc_failed
        if self.prefill_cobatch:
            # co-batch across buckets: pad every slot's chunk to the
            # largest bucket and run one step (per-row start/valid mask the
            # padding, so smaller rows' numerics are unchanged)
            items = cands
            bucket = max(prefill_bucket(take, self.chunk_len,
                                        self.min_bucket)
                         for _, _, _, take in items)
        else:
            # legacy: one bucket group per chunk step (the head's bucket)
            items, bucket = [], None
            for slot, st, start, take in cands:
                b = prefill_bucket(take, self.chunk_len, self.min_bucket)
                if bucket is None:
                    bucket = b
                if b == bucket:
                    items.append((slot, st, start, take))
        self.prefill_compile_keys.add(bucket)
        tok = np.zeros((self.n_slots, bucket), np.int32)
        start_v = np.ones((self.n_slots,), np.int32)   # >0: leave row alone
        valid_v = np.zeros((self.n_slots,), np.int32)  # 0: inactive row
        for slot, st, start, take in items:
            tok[slot, :take] = np.asarray(st.effective_tokens,
                                          np.int32)[start:start + take]
            start_v[slot] = start
            valid_v[slot] = take
        return items, bucket, tok, start_v, valid_v, alloc_failed

    def _prefill_tick(self, params, pool, sched: Scheduler, now: int,
                      cur_tok):
        """Run one compiled prefill-chunk step over the rows
        :meth:`_prefill_rows` builds, and scatter the first token of every
        prompt it completes into the device-resident decode input
        ``cur_tok``.

        Returns ``(dt, seq, nxt_dev, flag_dev, cur_tok, finished,
        alloc_failed)``: the step's dispatch wall time, its sequence number
        among the drain's steps, the (n_slots,) *device* greedy-token vector
        (no host readback — delivery is the caller's job) plus its
        non-finite tripwire flag vector, the updated decode input, the list
        of ``(slot, state)`` pairs whose prompt completed this tick (their
        next token is row ``slot`` of ``nxt_dev``; its ``out_tokens`` entry
        holds a ``None`` placeholder until the value lands on the host), and
        the states whose page allocation failed this tick (dropped from the
        step; the caller contains them). ``nxt_dev`` is None when every
        candidate's allocation failed — no step ran."""
        tel, live = self.telemetry, self.live_counters
        with NOSPAN if tel is None else tel.span("serve.prepare"):
            (items, bucket, tok, start_v, valid_v,
             alloc_failed) = self._prefill_rows(pool, sched)
        if not items:
            return 0.0, None, None, None, cur_tok, [], alloc_failed
        completes = np.zeros((self.n_slots,), bool)
        for slot, st, start, take in items:
            completes[slot] = start + take == st.effective_prompt_len
        n_tokens = int(valid_v.sum())
        seq = live.prefill_chunks + live.decode_steps
        t0 = time.perf_counter()
        sp = None if tel is None else tel.open(
            "serve.step_call", t0, kind="prefill", seq=seq, bucket=bucket,
            tokens=n_tokens, rows=len(items), start=start_v, valid=valid_v)
        if self.paged:
            logits, pool.caches = self.prefill_chunk_step(
                params, pool.caches, jnp.asarray(tok), jnp.asarray(start_v),
                jnp.asarray(valid_v), pool.block_tables_device())
        else:
            logits, pool.caches = self.prefill_chunk_step(
                params, pool.caches, jnp.asarray(tok), jnp.asarray(start_v),
                jnp.asarray(valid_v))
        nxt_dev = greedy_next_token(logits)
        flag_dev = nonfinite_rows(logits)
        dt = time.perf_counter() - t0
        if completes.any():
            cur_tok = merge_first_tokens(cur_tok, nxt_dev,
                                         jnp.asarray(completes))
        if sp is not None:
            tel.close(sp)
        live.prefill_chunks += 1
        live.prefill_tokens += n_tokens
        if self.paged and self.prefix_cache:
            # index the blocks this chunk filled (after dispatch: any
            # future matcher's chunks are dispatched later on the same
            # device stream, so they order after these writes)
            with NOSPAN if tel is None else tel.span("serve.prepare"):
                for slot, st, start, take in items:
                    pool.register_prefix(slot, start + take)
        finished = []
        for slot, st, start, take in items:
            st = sched.prefill_advance(slot, take, dt)
            if st.prefill_pos == st.effective_prompt_len:
                st = sched.finish_prefill(slot, None, now)
                finished.append((slot, st))
        return dt, seq, nxt_dev, flag_dev, cur_tok, finished, alloc_failed

    def serve(self, params, requests: Sequence[Request], *,
              sync: bool = False,
              on_token: Optional[Callable[[int, int, int], None]] = None,
              max_in_flight: int = 8) -> ServeSummary:
        """Drain ``requests`` (any arrival order) and return all results.

        The drain is a producer/consumer pipeline by default: the main
        thread dispatches device steps and enqueues each step's *device*
        token vector plus its host bookkeeping (which request gets which
        row), and a consumer thread turns queued vectors into host values —
        one batched ``jax.device_get`` per wakeup — filling each request's
        token list and firing ``on_token(rid, idx, token)``. The producer
        schedules purely by token *counts* (every request runs exactly
        ``max_new_tokens`` steps), so it never needs a token value and the
        per-step host readback disappears from the decode critical path;
        the device runs up to ``max_in_flight`` steps ahead of the host.

        ``sync=True`` keeps the legacy lockstep loop — every step's tokens
        are read back (and ``on_token`` fired) before the next step is
        dispatched — for readback-cost comparisons and the parity matrix.
        Both modes run the *same* device schedule and the same on-device
        argmax, so greedy tokens are bit-identical between them.

        ``on_token`` fires on the consumer thread in async mode (in
        submission order per request) and inline in sync mode; an exception
        it raises cancels the remaining requests, drains in-flight
        transfers, and re-raises from ``serve()``. :meth:`cancel`,
        :meth:`shutdown` and ``Request.timeout_steps`` take effect at tick
        granularity; cancelled/timed-out requests keep the tokens they had
        committed (``RequestResult.status`` records the outcome).
        """
        assert max_in_flight >= 1, max_in_flight
        if self.mesh is not None:
            from repro.serve.parallel import shard_serving_params
            params = shard_serving_params(self.model, params, self.mesh)
        pool = self._make_pool()
        sched = Scheduler()
        with self._ctl_lock:
            self._cancel_pending.clear()
            self._shutdown_flag = False
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            sched.submit(r)

        retired: list = []                 # RequestState, retirement order
        # device-resident decode input; rows refresh via on-device merges
        # (first tokens) and argmax outputs — never from the host. Vacant
        # rows hold stale tokens: their writes go to the trash block (paged)
        # or to a row the next first-chunk prefill fully resets (dense).
        cur_tok = jnp.zeros((self.n_slots, 1), jnp.int32)
        now = 0
        tel = self.telemetry
        live = self.live_counters = LiveCounters(pool, sched)
        readback_max = inflight_peak = 0
        t_first_decode = None
        peak_queue = peak_live = peak_blocks = peak_slots = 0
        # per-decode-step attention HBM read model (paged): the fused kernel
        # copies each row's live pages once (``kernel_steps``); the gather
        # path materializes every table slot of every row, so its traffic
        # scales with provisioned capacity
        attn_pages_fused = attn_pages_gather = 0
        kv_widths = (page_widths(self.model, pool.block_size) if self.paged
                     else ())
        decode_stall_steps = max_stall_run = stall_run = 0
        stall_s_run = 0.0
        stall_s: list = []            # per-decode-step injected prefill time
        adaptive_swaps: list = []     # plan swaps applied this drain
        # ---- fault tolerance bookkeeping ----
        inj = self.faults
        grail = self.guardrail
        faults_seen: dict = {}        # containment events by fault kind
        faults_contained = faults_failed = fault_retries = 0
        kernel_faults = 0             # step exceptions + hung steps
        degraded = False              # fused paged attention -> gather
        poison_watch: set = set()     # slots with an injected NaN in flight
        last_fault_error: Optional[BaseException] = None
        guardrail_swaps: list = []    # forced restores (numerical breach)

        def consult_adaptive():
            """Feed the controller this tick's counters; apply any swap.
            Runs exactly once per tick at the step boundary (before
            admission), so a swap can never land mid-step."""
            if self.paged:
                cap = pool.n_blocks - pool.n_shards
                occ = pool.blocks_in_use / max(cap, 1)
            else:
                occ = 1.0 - pool.n_free_slots / self.n_slots
            if stall_s:
                srt = np.sort(np.asarray(stall_s[-256:], np.float64))
                p99 = float(srt[min(len(srt) - 1, int(0.99 * len(srt)))])
            else:
                p99 = 0.0
            newplan = self.adaptive.observe(
                now, queue_depth=sched.queue_depth,
                blocked=sched.blocked_admissions,
                occupancy=occ, stall_p99=p99)
            if newplan is not None:
                self._swap_plan(newplan)
                adaptive_swaps.append({"step": int(now),
                                       "level": self.adaptive.level,
                                       "tau": self.adaptive.tau})

        # ---- host-side delivery plumbing (shared by both modes) ----
        q: "queue.Queue" = queue.Queue(maxsize=max_in_flight)
        consumer_err: list = []

        def deliver(arr, flags, deliveries):
            """Fill each (state, idx, slot) placeholder from a host token
            vector, check its non-finite tripwire flag, and fire the
            streaming callback."""
            t_now = time.perf_counter()
            for st, idx, slot in deliveries:
                tok = int(arr[slot])
                st.out_tokens[idx] = tok
                if (flags is not None and bool(flags[slot])
                        and st.fault_idx is None):
                    # device-side tripwire: the logit row that produced this
                    # token held NaN/inf. Stamp the first poisoned index;
                    # the producer contains the request at the next tick
                    # boundary (tokens before idx stay good).
                    st.fault_idx = idx
                    st.fault_kind = "nonfinite_logits"
                if idx == 0:
                    # honest TTFT, stamped at *delivery*: wall time from
                    # admission until the first token value landed on the
                    # host — under async that includes any pipeline lag,
                    # which is exactly what a streaming client experiences
                    st.ttft_s = t_now - st.wall_admitted
                if inj is not None:
                    try:
                        inj.on_deliver(st.request.rid, slot)
                    except InjectedFault:
                        # injected consumer error: contained per-request —
                        # the pinned user-callback contract (cancel all and
                        # re-raise) applies to *user* exceptions only
                        if st.fault_idx is None:
                            st.fault_idx = idx
                            st.fault_kind = "consumer_error"
                        continue
                suppressed = (st.fault_idx is not None
                              and idx >= st.fault_idx)
                if on_token is not None and not consumer_err and not suppressed:
                    on_token(st.request.rid, idx, tok)

        def consume():
            nonlocal readback_max
            stop = False
            while not stop:
                item = q.get()
                if item is None:
                    return
                batch = [item]
                while True:    # greedy drain: one device_get per wakeup
                    try:
                        more = q.get_nowait()
                    except queue.Empty:
                        break
                    if more is None:
                        stop = True
                        break
                    batch.append(more)
                arrs = jax.device_get([(tok, flg) for tok, flg, _ in batch])
                live.n_readbacks += 1
                live.steps_read_back += len(batch)
                readback_max = max(readback_max, len(batch))
                for (_, _, dl), (arr, flg) in zip(batch, arrs):
                    try:
                        deliver(arr, flg, dl)
                    except BaseException as e:  # noqa: BLE001
                        # keep draining so the producer never deadlocks on a
                        # full queue; re-raised from serve() after the join
                        consumer_err.append(e)

        def consume_guarded():
            try:
                consume()
            except BaseException as e:  # noqa: BLE001 — e.g. device_get died
                consumer_err.append(e)
                while q.get() is not None:      # unblock producer until STOP
                    pass

        consumer = None
        if not sync:
            consumer = threading.Thread(target=consume_guarded,
                                        name="serve-consumer", daemon=True)
            consumer.start()

        def emit(nxt_dev, flag_dev, deliveries, seq):
            """Hand step ``seq``'s tokens to delivery."""
            nonlocal readback_max, inflight_peak
            t0 = time.perf_counter()
            sp = None if tel is None else tel.open("serve.emit_wait", t0,
                                                   seq=seq)
            if sync:
                arr = np.asarray(nxt_dev)   # blocks on the device step
                flg = None if flag_dev is None else np.asarray(flag_dev)
            else:
                # blocks only at max_in_flight
                q.put((nxt_dev, flag_dev, deliveries))
            t1 = time.perf_counter()
            if sp is not None:
                tel.close(sp, t1)
            live.emit_wait_s += t1 - t0
            if sync:
                live.n_readbacks += 1
                live.steps_read_back += 1
                readback_max = 1
                try:
                    deliver(arr, flg, deliveries)
                except BaseException as e:  # noqa: BLE001 — user on_token
                    # same graceful shutdown as async mode: record the
                    # error, finish the drain (slots freed, pool books
                    # settled and reconciled), re-raise after
                    consumer_err.append(e)
            else:
                inflight_peak = max(inflight_peak, q.qsize())

        # ---- preemption: evict a live slot back to the waiting queue ----
        def evict(st):
            # the consumer may still be landing this slot's token values;
            # resume re-prefills prompt + generated-so-far, so every
            # committed placeholder must hold a real value first
            while any(t is None for t in st.out_tokens):
                if consumer_err:
                    return False  # shutting down; stop preempting
                time.sleep(2e-4)
            # freeing while earlier steps are in flight is safe: any reuse
            # of these blocks is written by a later-dispatched step, and
            # the device executes dispatches in order
            pool.free_slot(st.slot)
            sched.preempt(st, now)
            return True

        # ---- fault containment ----
        def flush_placeholders(st):
            """Wait out the consumer's in-flight deliveries for one state:
            retry resumes from prompt + tokens-so-far, so every committed
            placeholder must hold a real value before truncation. False on
            shutdown (consumer error) — nothing more will land."""
            while any(t is None for t in st.out_tokens):
                if consumer_err:
                    return False
                if sync:
                    # sync delivers inline; a residual None means the emit
                    # that would have filled it never ran — unreachable
                    # outside shutdown, but never spin on it
                    return False
                time.sleep(2e-4)
            return True

        def maybe_degrade():
            """Past ``kernel_fault_limit`` step faults, fall back from the
            fused paged-attention kernel to the gather reference path: a
            dispatch switch through the ``get_serving_step`` memo (the key
            includes ``paged_attn``), never a mid-drain recompile — and the
            parity matrix pins fused/gather greedy tokens bit-identical, so
            the degraded drain's tokens don't change."""
            nonlocal degraded
            if (not degraded and self.paged and self.paged_attn == "fused"
                    and kernel_faults >= self.kernel_fault_limit):
                degraded = True
                self.paged_attn = "gather"
                self.decode_step = get_serving_step(
                    self.model, "paged_decode", mp=self.mp,
                    paged_attn="gather", donate=self._donate,
                    mesh_layout=self.mesh_layout)

        def contain(st, kind=None, quarantine=None):
            """Contain one faulted request: settle its in-flight
            deliveries, truncate its tokens to the last-known-good prefix,
            quarantine its KV pages when the fault may have poisoned them,
            and either requeue it for a bounded retry (re-prefilling prompt
            + surviving tokens through the bit-exact resume path, so a
            retried request that completes matches a fault-free run) or
            retire it ``failed`` with the partial tokens."""
            nonlocal faults_contained, faults_failed, fault_retries
            if st.status == WAITING:
                return              # already contained this sweep
            kind = kind or st.fault_kind or "fault"
            if not flush_placeholders(st):
                return              # shutting down; apply_control retires
            was_done = st.status == DONE
            if was_done and st.result_status not in ("ok", "retried"):
                return              # cancelled/timed out: terminal
            faults_seen[kind] = faults_seen.get(kind, 0) + 1
            if st.fault_idx is not None:
                # drop the poisoned suffix (placeholders included — flush
                # guaranteed values landed, truncation regrows the step debt
                # through remaining_new_tokens)
                del st.out_tokens[st.fault_idx:]
            if quarantine is None:
                quarantine = kind in ("nonfinite_logits", "nan_page")
            if st.status in (PREFILLING, RUNNING):
                if st.slot in poison_watch:
                    # an injected NaN is in flight for this slot: whatever
                    # fault got here first (alloc failure, step exception),
                    # its pages are poisoned — releasing them to the free
                    # list would leak the NaN into reallocated requests
                    quarantine = True
                poison_watch.discard(st.slot)
                if self.paged and quarantine:
                    # the slot's pages may hold NaN/inf: pull every one out
                    # of circulation (de-indexed, COW-forked away from any
                    # borrower, never returned to the free list)
                    pool.quarantine_slot(st.slot)
                pool.free_slot(st.slot)
            retry = (st.n_retries < self.max_retries
                     and kind != "consumer_error")
            if retry:
                if was_done:
                    # the flag landed after deadline retirement: un-retire
                    # and redo the poisoned tail
                    retired.remove(st)
                sched.requeue_for_retry(st, now)
                fault_retries += 1
                faults_contained += 1
            else:
                faults_failed += 1
                st.fault_kind = kind
                if was_done:
                    st.result_status = "failed"
                else:
                    retired.append(sched.retire(st, now, "failed"))

        def apply_faults():
            """Producer-side containment sweep, run at tick boundaries:
            contain every request the consumer's tripwire (or an injected
            delivery fault) has stamped since the last sweep."""
            hit = [st for st in sched.states.values()
                   if st.fault_idx is not None and st.status != WAITING]
            for st in hit:
                contain(st)

        def impossible(st):
            """Quarantine shrank the pool below this request's worst-case
            block need: fail it gracefully instead of crashing the drain."""
            nonlocal faults_failed
            faults_seen["impossible_request"] = (
                faults_seen.get("impossible_request", 0) + 1)
            faults_failed += 1
            sched.remove_waiting(st.request.rid)
            retired.append(sched.retire(st, now, "failed"))

        # ---- control plane: cancellation / timeouts / shutdown ----
        def cancel_live(st, status, now):
            if st.status == WAITING:
                sched.remove_waiting(st.request.rid)
            elif st.status in (PREFILLING, RUNNING):
                pool.free_slot(st.slot)
            retired.append(sched.retire(st, now, status))

        def apply_control(now):
            with self._ctl_lock:
                todo = self._cancel_pending
                self._cancel_pending = set()
                shutdown = self._shutdown_flag
            # a callback error is an implicit shutdown: stop scheduling new
            # work, drain what's in flight, re-raise after the join
            shutdown = shutdown or bool(consumer_err)
            for st in list(sched.states.values()):
                t = st.request.timeout_steps
                if (st.status != DONE and t is not None
                        and now >= st.request.arrival + t):
                    cancel_live(st, "timeout", now)
            if shutdown:
                todo = set(sched.states)
            for rid in sorted(todo):
                st = sched.states.get(rid)
                if st is not None and st.status != DONE:
                    cancel_live(st, "cancelled", now)

        def admit():
            with NOSPAN if tel is None else tel.span("serve.admit") as sp:
                n = self._admit(params, pool, sched, now,
                                evict if self.preemption else None,
                                on_impossible=impossible)
                if sp is not None:
                    sp.attrs["admitted"] = n

        t_start = time.perf_counter()
        tick = None            # the open serve.tick span
        try:
            while True:
                if tel is not None:
                    # ticks tile the loop: each closes as the next opens
                    if tick is not None:
                        tel.close(tick)
                    tick = tel.open("serve.tick", tick=now)
                if not sched.has_work():
                    # drain-end pipeline flush: a tripwire flag still in
                    # flight can re-queue a retry — settle every in-flight
                    # delivery, sweep once more, and only then stop
                    for st in list(retired):
                        flush_placeholders(st)
                    apply_faults()
                    if not sched.has_work():
                        break
                apply_control(now)
                if not sched.has_work():
                    continue
                if inj is not None:
                    inj.tick(now)
                apply_faults()
                if not sched.has_work():
                    continue
                if self.adaptive is not None:
                    consult_adaptive()
                admit()
                peak_queue = max(peak_queue, sched.queue_depth)
                # prefill phase — TTFT-aware arbitration: prefill freely
                # while nothing is decoding, else at most chunk_budget chunk
                # steps per decode step so no decode slot stalls unboundedly
                chunks_this_tick = 0
                while sched.prefilling and (not sched.running
                                            or chunks_this_tick
                                            < self.chunk_budget):
                    was_decoding = bool(sched.running)
                    try:
                        if (inj is not None
                                and inj.on_step("prefill") == "hung"):
                            kernel_faults += 1
                            maybe_degrade()
                        (dt, seq, nxt_dev, flag_dev, cur_tok, finished,
                         alloc_failed) = self._prefill_tick(
                            params, pool, sched, now, cur_tok)
                    except InjectedFault as e:
                        # step blew up before any cache write: contain every
                        # prefilling slot (bounded retry re-prefills from
                        # scratch, so no page can be half-written)
                        last_fault_error = e
                        kernel_faults += 1
                        maybe_degrade()
                        for st in list(sched.prefilling.values()):
                            contain(st, kind="step_exception",
                                    quarantine=False)
                        chunks_this_tick += 1
                        continue
                    for st in alloc_failed:
                        contain(st, kind="alloc_failure", quarantine=False)
                    if nxt_dev is None:     # every candidate's alloc failed
                        chunks_this_tick += 1
                        continue
                    chunks_this_tick += 1
                    if was_decoding:
                        decode_stall_steps += 1
                        stall_run += 1
                        max_stall_run = max(max_stall_run, stall_run)
                        stall_s_run += dt
                    if finished:
                        # the first tokens are in the device-resident decode
                        # input already; ship the same vector to the host
                        # for delivery. Resumed requests already hold
                        # delivered tokens: the placeholder finish_prefill
                        # appended is the last entry, not necessarily index 0
                        deliveries = [(st, len(st.out_tokens) - 1, slot)
                                      for slot, st in finished]
                        emit(nxt_dev, flag_dev, deliveries, seq)
                        for slot, st in finished:
                            if st.done:          # max_new_tokens == 1
                                retired.append(sched.retire(st, now))
                                pool.free_slot(slot)
                    # a finished 1-token request frees its slot immediately:
                    # let a queued request claim it this same tick
                    admit()
                if sched.running:
                    with NOSPAN if tel is None else tel.span(
                            "serve.prepare"):
                        # fresh array every tick: jnp.asarray may be
                        # zero-copy on CPU, and an in-flight step from a
                        # previous tick could still alias a reused buffer
                        # we'd be zeroing
                        pos_host = np.zeros((self.n_slots,), np.int32)
                        alloc_bad = []
                        for slot, st in sched.running.items():
                            pos_host[slot] = st.next_pos
                            if self.paged:
                                try:
                                    if inj is not None:
                                        inj.on_alloc(slot)
                                    pool.ensure_block(slot, st.next_pos)
                                except (InjectedFault, PoolExhausted) as e:
                                    last_fault_error = e
                                    alloc_bad.append(st)
                        for st in alloc_bad:
                            contain(st, kind="alloc_failure",
                                    quarantine=False)
                        if not sched.running:   # every page alloc failed
                            now += 1
                            continue
                        # live tokens after this step: everything written so
                        # far (next_pos) plus the write this step performs
                        live_now = sum(st.next_pos + 1
                                       for st in sched.running.values())
                        peak_live = max(peak_live, live_now)
                        peak_slots = max(peak_slots, len(sched.running))
                        if self.paged:
                            peak_blocks = max(peak_blocks,
                                              pool.blocks_in_use)
                            live.decode_live_token_steps += live_now
                            # the kernel's view: vacant rows hold length 1
                            steps, copy_steps, pages = kernel_steps(
                                pos_host + 1, block_size=pool.block_size,
                                n_pages=pool.max_blocks,
                                widths=kv_widths)
                            if self.paged_attn == "fused":
                                live.decode_kernel_grid_steps += steps
                                live.decode_kernel_copy_steps += copy_steps
                            attn_pages_fused += pages
                            attn_pages_gather += (self.n_slots
                                                  * pool.max_blocks)
                        bt = None
                        if self.paged:
                            # decode sees block tables only for *running*
                            # rows: a slot mid-prefill owns real blocks, and
                            # the vacant-row garbage write must go to the
                            # trash block, not into K/V its earlier chunks
                            # wrote
                            bt_host = pool.block_tables.copy()
                            for s in range(self.n_slots):
                                if s not in sched.running:
                                    bt_host[s] = -1
                            bt = jnp.asarray(bt_host)
                    # injected numeric poisons for this step: a NaN'd KV
                    # page is written *before* dispatch (the step reads it
                    # back through attention), a NaN'd logit row is applied
                    # to the step's output below
                    nan_rows = None
                    if inj is not None:
                        for spec in inj.take_poisons():
                            slots = sorted(sched.running)
                            slot = (spec.slot if spec.slot in sched.running
                                    else slots[0])
                            if spec.kind == "nan_page" and self.paged:
                                row = pool.block_tables[slot]
                                held = [int(b) for b in row
                                        if int(b) >= 0
                                        and int(b) % pool.blocks_per_shard]
                                if held:
                                    blk = held[min(spec.page, len(held) - 1)]
                                    pool.poison_block(blk)
                                    poison_watch.add(slot)
                            else:   # nan_logits (nan_page degrades to it
                                    # in dense mode — no pages to poison)
                                if nan_rows is None:
                                    nan_rows = np.zeros((self.n_slots,),
                                                        bool)
                                nan_rows[slot] = True
                                poison_watch.add(slot)
                    shadow = None
                    if (grail is not None and self.mp
                            and grail.restored_at is None
                            and live.decode_steps % grail.every == 0):
                        # tau-anchored shadow: one high-precision decode
                        # step over the same inputs before the real step
                        # touches them (donate=False — its cache writes are
                        # discarded), MSE'd below against the active plan's
                        # logits for one sampled live row
                        rows = sorted(sched.running)
                        grail_row = rows[(live.decode_steps // grail.every)
                                         % len(rows)]
                        ref_step = get_serving_step(
                            self.model,
                            "paged_decode" if self.paged else "decode",
                            mp=None,
                            paged_attn=(self.paged_attn if self.paged
                                        else None),
                            donate=False, mesh_layout=self.mesh_layout)
                        if self.paged:
                            s_logits, _ = ref_step(
                                params, pool.caches, cur_tok,
                                jnp.asarray(pos_host), bt)
                        else:
                            s_logits, _ = ref_step(
                                params, pool.caches, cur_tok,
                                jnp.asarray(pos_host))
                        shadow = (s_logits, grail_row)
                    seq = live.prefill_chunks + live.decode_steps
                    t0 = time.perf_counter()
                    if t_first_decode is None:
                        t_first_decode = t0
                    sp = None if tel is None else tel.open(
                        "serve.step_call", t0, kind="decode", seq=seq,
                        bucket=1, tokens=len(sched.running),
                        rows=len(sched.running),
                        pos=pos_host[sorted(sched.running)])
                    try:
                        if (inj is not None
                                and inj.on_step("decode") == "hung"):
                            kernel_faults += 1
                            maybe_degrade()
                        if self.paged:
                            logits, pool.caches = self.decode_step(
                                params, pool.caches, cur_tok,
                                jnp.asarray(pos_host), bt)
                        else:
                            logits, pool.caches = self.decode_step(
                                params, pool.caches, cur_tok,
                                jnp.asarray(pos_host))
                    except InjectedFault as e:
                        # the step never dispatched — caches are intact;
                        # contain every running request (bounded retry
                        # re-prefills prompt + tokens-so-far)
                        if sp is not None:
                            tel.close(sp)
                        last_fault_error = e
                        kernel_faults += 1
                        maybe_degrade()
                        for st in list(sched.running.values()):
                            contain(st, kind="step_exception",
                                    quarantine=False)
                        now += 1
                        continue
                    if shadow is not None:
                        s_logits, grail_row = shadow
                        # fp32 logit MSE for the sampled row — one blocking
                        # scalar readback per `every` steps. A NaN MSE (a
                        # poison fault, not a quantization breach) never
                        # trips the comparison.
                        mse = float(shadow_logit_mse(logits, s_logits,
                                                     grail_row))
                        budget = grail.budget_for(self._mp_plan)
                        if grail.observe_mse(now, mse, budget):
                            # measured loss-MSE breached margin * budget —
                            # eq. 23's tau constraint, enforced live: force
                            # a restore to the base plan at this boundary
                            if self.adaptive is not None:
                                self._swap_plan(
                                    self.adaptive.force_restore(now))
                            else:
                                self._swap_plan(None)
                            guardrail_swaps.append(
                                {"step": int(now), "mse": mse,
                                 "budget": budget})
                    if nan_rows is not None and nan_rows.any():
                        logits = poison_logit_rows(logits,
                                                   jnp.asarray(nan_rows))
                    nxt_dev = greedy_next_token(logits)
                    flag_dev = nonfinite_rows(logits)
                    cur_tok = nxt_dev[:, None]
                    if sp is not None:
                        tel.close(sp)
                    live.decode_steps += 1
                    if self.paged:
                        live.decode_capacity_token_steps += (
                            self.n_slots * pool.max_blocks * pool.block_size)
                    deliveries = []
                    for slot in list(sched.running):
                        st = sched.running[slot]
                        deliveries.append((st, len(st.out_tokens), slot))
                        sched.record_token(slot, None)
                    emit(nxt_dev, flag_dev, deliveries, seq)
                    live.decode_s += time.perf_counter() - t0
                    stall_s.append(stall_s_run)
                    stall_s_run = 0.0
                    stall_run = 0
                    # deadline-based retirement: a request is done after
                    # exactly max_new_tokens scheduled steps — the host
                    # never inspects token values to decide
                    for slot in list(sched.running):
                        st = sched.running[slot]
                        if st.done:
                            if slot in poison_watch:
                                # an injected NaN targeted this row: settle
                                # its deliveries now so the tripwire flag
                                # cannot lose the race against deadline
                                # retirement (which frees the very pages
                                # quarantine must capture)
                                poison_watch.discard(slot)
                                flush_placeholders(st)
                                if st.fault_idx is not None:
                                    contain(st)
                                    continue
                            retired.append(sched.retire(st, now))
                            pool.free_slot(slot)
                    now += 1
                elif not sched.prefilling:
                    # idle: jump the clock to the next arrival, don't spin
                    nxt_arrival = sched.next_arrival()
                    if nxt_arrival is None:
                        break
                    now = max(now + 1, nxt_arrival)
        finally:
            if tick is not None:
                tel.close(tick)
            if consumer is not None:
                # drain: everything emitted gets delivered before we return.
                # Counted separately from host_blocked_s — this wait overlaps
                # no dispatchable work (the schedule is complete), so it is
                # not critical-path blocking, just the pipeline emptying
                t0 = time.perf_counter()
                q.put(None)
                consumer.join()
                live.drain_wait_s += time.perf_counter() - t0

        t_drain_end = time.perf_counter()
        total_s = t_drain_end - t_start
        if consumer_err:
            if self.paged:
                # a callback error aborts mid-flight: slots were freed by
                # the shutdown cancellations, but a delivery that died
                # half-way can leave refcounts ahead of the tables — settle
                # the books so the pool is reusable after the re-raise
                pool.reconcile()
            raise consumer_err[0]
        decode_s = live.decode_s
        if not sync and t_first_decode is not None:
            # async decode_s: the producer only measured dispatch time, so
            # report the wall span from the first decode dispatch to drain
            # end (device compute, interleaved prefill, and overlapped
            # readbacks) — the honest denominator for pipelined throughput
            decode_s = max(decode_s, t_drain_end - t_first_decode)
        results = {st.request.rid: sched.materialize(st) for st in retired}
        # decode-produced tokens (each request's first token is prefill's)
        n_decoded = sum(max(len(r.tokens) - 1, 0) for r in results.values())
        counters = {
            "paged": self.paged,
            "mesh": (None if self.mesh_layout is None else
                     {"data": self.mesh_layout.data,
                      "model": self.mesh_layout.model,
                      "shard_pages": self.mesh_layout.shard_pages}),
            # wall-clock throughput over the *identical* window in sync and
            # async modes (submission to drain end) — the fair pipelined-vs-
            # sync comparison; ``tokens_per_s`` keeps the decode-phase-only
            # denominator, which is measured differently in the two modes
            "wall_tokens_per_s": (n_decoded / total_s if total_s > 0 else 0.0),
            "peak_queue_depth": peak_queue,
            "blocked_admissions": sched.blocked_admissions,
            "peak_live_tokens": peak_live,
            "peak_slots_in_use": peak_slots,
            "dense_kv_bytes": self.n_slots * dense_slot_bytes(self.model,
                                                              self.max_len),
            # chunked/bucketed prefill economics + decode-stall signals
            "prefill_chunks": live.prefill_chunks,
            "prefill_tokens": live.prefill_tokens,
            "prefill_cobatch": bool(self.prefill_cobatch),
            # priority scheduling: evictions back to the waiting queue
            "preemptions": sched.preemptions,
            "decode_stall_steps": decode_stall_steps,
            "max_decode_stall_run": max_stall_run,
            "prefill_buckets": len(self.prefill_compile_keys),
            "distinct_prompt_lens": len(self.prompt_lens_seen),
            # host/device overlap: how long the producer thread sat blocked
            # on token transfers *on the decode critical path* (sync: every
            # step's readback; async: queue backpressure only — the final
            # drain is drain_wait_s, overlapping no dispatchable work), how
            # readbacks batched, and how far the device ran ahead of the host
            "sync": bool(sync),
            "host_blocked_s": live.emit_wait_s,
            "host_blocked_s_per_step": (live.emit_wait_s
                                        / max(live.decode_steps, 1)),
            "drain_wait_s": live.drain_wait_s,
            "n_readbacks": live.n_readbacks,
            "readback_batch_max": readback_max,
            "readback_batch_mean": (live.steps_read_back / live.n_readbacks
                                    if live.n_readbacks else 0.0),
            "steps_in_flight_peak": inflight_peak,
            "n_cancelled": sum(1 for st in retired
                               if st.result_status in ("cancelled",
                                                       "timeout")),
            "n_failed": sum(1 for st in retired
                            if st.result_status == "failed"),
            "n_retried": sum(1 for st in retired
                             if st.result_status == "retried"),
        }
        counters["faults"] = {
            "injected": dict(inj.fired) if inj is not None else {},
            "seen": dict(faults_seen),
            "contained": faults_contained,
            "retries": fault_retries,
            "failed": faults_failed,
            "kernel_faults": kernel_faults,
            "degraded_paged_attn": degraded,
            "quarantined_blocks": (pool.quarantined_blocks
                                   if self.paged else 0),
            "last_error": (repr(last_fault_error)
                           if last_fault_error is not None else None),
        }
        if grail is not None:
            counters["guardrail"] = {
                "every": grail.every,
                "margin": grail.margin,
                "checks": grail.checks,
                "breaches": grail.breaches,
                "last_mse": grail.last_mse,
                "restored_at": grail.restored_at,
                "swaps": list(guardrail_swaps),
            }
        if self.adaptive is not None:
            counters["adaptive"] = {
                "taus": list(self.adaptive.taus),
                "final_level": self.adaptive.level,
                "final_tau": self.adaptive.tau,
                "downshifts": self.adaptive.downshifts,
                "restores": self.adaptive.restores,
                "swaps": list(adaptive_swaps),
            }
        if stall_s:
            arr = np.sort(np.asarray(stall_s, np.float64))
            counters["decode_stall_p50_s"] = float(arr[len(arr) // 2])
            counters["decode_stall_p99_s"] = float(
                arr[min(len(arr) - 1, int(0.99 * len(arr)))])
        if self.paged:
            blk_bytes = paged_block_bytes(self.model, pool.block_size)
            # slot-major SSM state is allocated per slot up front in paged
            # mode too — include it so the dense comparison is symmetric
            slot_bytes = paged_slot_bytes(self.model, pool.block_size)
            counters.update(
                block_size=pool.block_size, n_blocks=pool.n_blocks,
                peak_blocks_in_use=peak_blocks,
                free_blocks_final=pool.n_free_blocks,
                kv_bytes_per_block=blk_bytes,
                peak_kv_bytes=(peak_blocks * blk_bytes
                               + self.n_slots * slot_bytes),
                # modeled per-drain attention K/V HBM reads across all
                # decode steps: what the active path read, plus both models
                # so one run exposes the fused-vs-gather ratio. Live tokens
                # summed per step (vs the provisioned per-step capacity)
                # give the occupancy these byte models scale with.
                paged_attn=self.paged_attn,
                decode_attn_bytes_read=(
                    attn_pages_fused if self.paged_attn == "fused"
                    else attn_pages_gather) * blk_bytes,
                decode_attn_bytes_fused_model=attn_pages_fused * blk_bytes,
                decode_attn_bytes_gather_model=attn_pages_gather * blk_bytes,
                decode_live_token_steps=live.decode_live_token_steps,
                decode_capacity_token_steps=live.decode_capacity_token_steps,
                # prefix cache economics: tokens whose prefill was skipped
                # because a resident block chain already held them
                prefix_cache=bool(self.prefix_cache),
                prefix_hit_requests=pool.prefix_hit_requests,
                prefix_hit_blocks=pool.prefix_hit_blocks,
                prefix_hit_tokens=pool.prefix_hit_tokens,
                cow_forks=pool.cow_forks,
                reclaimed_cached_blocks=pool.reclaimed_cached_blocks)
        else:
            counters["peak_kv_bytes"] = counters["dense_kv_bytes"]
        # throughput over the decode phase only: each request's first token
        # comes out of its prefill, whose wall time is accounted as TTFT
        return ServeSummary(results=results, n_steps=live.decode_steps,
                            decode_s=decode_s, total_s=total_s,
                            tokens_per_s=(n_decoded / decode_s
                                          if decode_s > 0 else 0.0),
                            counters=counters)
