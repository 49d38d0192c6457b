"""KV/SSM cache pools for continuous batching: dense slots and paged blocks.

:class:`CachePool` (dense) owns one device cache tree whose leading (batch)
axis is the slot axis: ``n_slots`` independent sequences decode together in a
single compiled step, each slot a monolithic ``max_len`` ring. HBM scales
with ``n_slots * max_len`` regardless of live tokens.

:class:`PagedCachePool` (vLLM-style) replaces the per-slot rings with a
shared store of fixed-size blocks: attention K/V lives in block-major arrays
``(n_blocks, heads, block_size, width)``, each slot maps its logical pages
to physical blocks through a host-side block table, and blocks are
allocated on demand as decode crosses block boundaries — HBM scales with
*live tokens*, so an MP plan's fp8 ``kv_cache_dtype`` savings buy
proportionally more concurrent slots. Admission reserves a request's worst-case block count (prompt +
``max_new_tokens - 1`` writes), which makes mid-decode allocation infallible
while materializing blocks lazily; :meth:`can_admit` returning False is the
scheduler's backpressure signal. Physical block 0 is never allocated: it is
the trash block that absorbs writes from vacant decode rows (block-table
entries of -1 clamp to 0 inside the kernel). SSM state has no sequence axis
and stays slot-major.

Blocks are **refcounted** and may be shared read-only between slots
(automatic prefix caching, vLLM / RadixAttention precedent): prompts are
content-hashed block by block with *chained* digests
(``h_j = sha256(h_{j-1} || tokens_j)``), so one digest match implies the
whole prefix up to that block matches. A new request whose chain matches
resident blocks claims them (refcount + 1), maps them into its table, and
skips prefill for the matched tokens entirely. The last write into a shared
block triggers a **copy-on-write fork** (:meth:`ensure_range` detects a
write landing on a borrowed page): the block is duplicated on device
(:meth:`~repro.models.lm.LM.paged_copy_block`), the slot's table repoints at
the private copy, and the parent chain stays immutable. When a slot is
freed, indexed blocks whose refcount hits zero stay *cached* (content
resident, LRU-reclaimable) instead of returning to the free list —
:meth:`_alloc_block` reclaims the oldest cached block (de-indexing it) only
when the free list runs dry. ``n_free_blocks`` therefore counts free +
cached: both are allocatable capacity.

All allocation is host-side free lists; device traffic goes through
:meth:`insert` / the COW fork (one jitted scatter/copy each, traced over
slot/block ids).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["CachePool", "PagedCachePool", "PoolExhausted",
           "dense_slot_bytes", "paged_block_bytes", "paged_slot_bytes"]


def dense_slot_bytes(model, max_len: int) -> int:
    """HBM bytes one dense cache slot (KV rings + SSM state) pins at
    ``max_len`` — the dense baseline cost of a slot whether or not it holds
    live tokens."""
    from repro.nn.spec import param_bytes
    return param_bytes(model.cache_specs(1, max_len))


def paged_block_bytes(model, block_size: int) -> int:
    """HBM bytes one KV block adds across all layers (the marginal cost of
    ``block_size`` live tokens under paging)."""
    from repro.nn.spec import param_bytes
    return (param_bytes(model.paged_cache_specs(1, 2, block_size))
            - param_bytes(model.paged_cache_specs(1, 1, block_size)))


def page_widths(model, block_size: int) -> set:
    """Last dims of the model's paged K/V entries (the leaves whose leading
    axis is the pool's blocks)."""
    return {s.shape[-1] for s in model.paged_cache_specs(
        1, 1, block_size).values() if s.logical_axes[0] == "kv_blocks"}


def paged_slot_bytes(model, block_size: int) -> int:
    """HBM bytes one *slot* pins under paging regardless of live tokens:
    the slot-major leaves (SSM state on mamba/hybrid archs; zero for pure
    attention). Counted so paged-vs-dense comparisons stay symmetric."""
    from repro.nn.spec import param_bytes
    return (param_bytes(model.paged_cache_specs(2, 1, block_size))
            - param_bytes(model.paged_cache_specs(1, 1, block_size)))


@jax.jit
def _scatter_slot(pool: dict, one: dict, slot: jax.Array) -> dict:
    def upd(pl, ol):
        start = (slot,) + (0,) * (pl.ndim - 1)
        return jax.lax.dynamic_update_slice(pl, ol.astype(pl.dtype), start)
    return jax.tree.map(upd, pool, one)


class PoolExhausted(RuntimeError):
    """No free slot or block for this request right now: admission
    backpressure, not a device fault. Engines catch exactly this — a
    ``jax.errors.JaxRuntimeError`` (device OOM, a failed compile) is also a
    ``RuntimeError`` and must propagate, not retire requests as failed."""


# one jitted copy-on-write fork per (model, layout): pools are rebuilt per
# serve() drain, so the jit cache must outlive the pool instance or every
# drain recompiles. Values keep strong refs so id() keys stay valid.
_COW_JIT_CACHE: dict = {}


class CachePool:
    """``n_slots`` x ``max_len`` dense KV/SSM cache slots for one model.

    A freshly prefilled single-request cache (batch=1) is scattered into a
    slot with one jitted ``dynamic_update_slice`` per leaf; because the
    insert overwrites the *entire* slot row — including the ring-buffer
    ``pos`` entries that gate the attention mask — stale K/V from the slot's
    previous occupant can never leak into a new request.
    """

    def __init__(self, model, n_slots: int, max_len: int, mesh_layout=None,
                 chunk_extra: int = 0):
        assert n_slots >= 1 and max_len >= 1, (n_slots, max_len)
        self.n_slots = n_slots
        self.max_len = max_len
        # chunk_extra widens windowed rings by the prefill chunk length so
        # dense chunked prefill never truncates a chunk that straddles the
        # window boundary (see kv_cache_spec); 0 keeps the legacy shapes
        kw = {"chunk_extra": chunk_extra} if chunk_extra else {}
        self.caches = model.init_cache(n_slots, max_len, **kw)
        if mesh_layout is not None:
            from repro.serve.parallel import shard_cache_tree
            self.caches = shard_cache_tree(
                model, self.caches, model.cache_specs(n_slots, max_len, **kw),
                mesh_layout.mesh)
        self._free = list(range(n_slots - 1, -1, -1))  # pop() yields slot 0 first

    # ---- host-side slot accounting ----
    @property
    def n_free(self) -> int:
        return len(self._free)

    # uniform pool interface (shared with PagedCachePool)
    @property
    def n_free_slots(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        """Claim a free slot index; raises PoolExhausted when the pool is
        full."""
        if not self._free:
            raise PoolExhausted("cache pool exhausted")
        return self._free.pop()

    def free(self, slot: int) -> None:
        assert 0 <= slot < self.n_slots and slot not in self._free, slot
        self._free.append(slot)

    def free_slot(self, slot: int) -> None:
        self.free(slot)

    # ---- device-side slot contents ----
    def insert(self, slot: int, request_cache: dict) -> None:
        """Scatter a batch=1 cache tree into ``slot`` (overwrites the row).
        Not on the engine's serving path since the chunked-prefill rewrite
        (dense prefill now writes the pool row in place); kept as the
        generic cache-injection API and covered by the pool tests."""
        self.caches = _scatter_slot(self.caches, request_cache,
                                    jnp.asarray(slot, jnp.int32))

    # uniform pool interface: dense slots carry no cross-drain state
    def reset_counters(self) -> None:
        pass

    def invalidate_prefix_index(self) -> None:
        pass


class PagedCachePool:
    """Paged KV storage: ``n_blocks`` blocks of ``block_size`` tokens shared
    by ``n_slots`` decode rows through per-slot block tables.

    Invariants the attention kernel relies on (see ``nn/layers.py``):

    * a block has exactly one *writer* at a time (block 0 by nobody — it is
      the trash sink for vacant rows), but may have many concurrent
      *readers*: a refcounted prefix block appears in several slots' tables
      and every logical position <= each slot's write position holds valid
      token data for that slot, because a shared block's content is
      bit-identical to what each sharer's own prefill would have written
      (per-token quant scales make K/V a pure function of the tokens at and
      before each position);
    * a slot never writes a shared block: the only write that could land in
      one (the tail chunk of a fully-matched prompt) forks it first
      (copy-on-write), and decode writes always target pages past the
      matched prefix;
    * a slot's pages are allocated in logical order and written
      contiguously, so the causal mask alone separates live keys from stale
      block contents — freed blocks need no device-side scrubbing before
      reuse.

    Admission accounting: :meth:`alloc_slot` *reserves* the request's
    worst-case block count without materializing it; :meth:`ensure_block` /
    :meth:`ensure_range` then draw on the reservation as prefill/decode
    cross block boundaries. Matched prefix blocks are claimed instead of
    reserved (refcount + 1, no new capacity), shrinking the reservation by
    one block per hit. ``can_admit`` is False while no shard's
    free-plus-cached-minus-reserved budget covers a new request — the
    backpressure signal the scheduler turns into queueing or preemption.

    Prefix index: per shard, ``digest -> block`` for fully-written prompt
    blocks (chained sha256 over the block's tokens — see
    :meth:`prefix_digests`). Blocks whose refcount drops to zero while
    indexed move to a per-shard cached-LRU (content resident, allocatable);
    :meth:`_alloc_block` reclaims the least recently released cached block
    — de-indexing it, which truncates any chain through it — only after the
    free list empties, so resident prefixes survive as long as capacity
    allows. Eviction therefore never reclaims a block with a nonzero
    refcount.

    Mesh sharding: with a ``mesh_layout`` whose ``shard_pages`` is set, the
    physical pool splits into ``data`` equal shards — shard ``d`` owns the
    contiguous page range ``[d*bps, (d+1)*bps)`` plus its own trash block at
    ``d*bps`` — and every slot draws blocks exclusively from its own shard
    (slot ``s`` lives on shard ``s // slots_per_shard``, matching the
    contiguous slot-axis sharding over ``data``). The prefix index is
    per-shard for the same reason: a slot can only map blocks that live on
    its own data shard, so a prefix resident on another shard is a miss.
    Admission planning (:meth:`_plan_admission`) is shard-aware twice over:
    it gates on *per-shard* free-list pressure (one hot shard cannot strand
    the others' capacity) and places a request on the shard where its
    prefix chain is longest. Block tables keep *global* ids; the shard_map
    kernel path translates them to shard-local ids. With one shard the
    allocator is bit-for-bit the single-device one (same free lists, same
    pop order).
    """

    def __init__(self, model, n_slots: int, max_len: int,
                 block_size: int = 16, n_blocks=None, mesh_layout=None,
                 data_shards: int = 1):
        assert n_slots >= 1 and max_len >= 1 and block_size >= 1
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.layout = mesh_layout
        self.max_blocks = -(-max_len // block_size)     # table width per slot
        # data_shards is the host-accounting hook for testing the sharded
        # allocator without devices; with a real mesh the layout wins
        data = mesh_layout.data if mesh_layout is not None else data_shards
        n_blocks, shard_pages, bps = self.plan_blocks(
            n_slots, max_len, block_size, n_blocks=n_blocks, data_shards=data)
        if mesh_layout is not None:
            assert (n_blocks, shard_pages) == (mesh_layout.n_blocks,
                                               mesh_layout.shard_pages), \
                "pool geometry disagrees with the serving mesh layout"
        self.n_blocks = n_blocks
        self.n_shards = data if shard_pages else 1
        self.blocks_per_shard = bps
        self.slots_per_shard = n_slots // self.n_shards
        self.caches = model.init_paged_cache(n_slots, n_blocks, block_size)
        if mesh_layout is not None:
            from repro.serve.parallel import shard_cache_tree
            self.caches = shard_cache_tree(
                model, self.caches,
                model.paged_cache_specs(n_slots, n_blocks, block_size),
                mesh_layout.mesh)
        self._insert_fn = jax.jit(model.paged_insert)
        # per-shard free lists; shard d's trash block d*bps is never listed
        # (single shard: blocks [1, n_blocks), trash 0 — the legacy layout)
        self._free_blocks_by_shard = [
            list(range((d + 1) * bps - 1, d * bps, -1))
            for d in range(self.n_shards)]
        self._free_slots_by_shard = [
            list(range((d + 1) * self.slots_per_shard - 1,
                       d * self.slots_per_shard - 1, -1))
            for d in range(self.n_shards)]
        self._reserved_by_shard = [0] * self.n_shards
        self._slot_reserve: dict = {}       # slot -> outstanding reservation
        self._slot_blocks: dict = {}        # slot -> [referenced block ids]
        self.block_tables = np.full((n_slots, self.max_blocks), -1, np.int32)
        # ---- prefix sharing state ----
        self._ref: dict = {}                # block -> refcount (materialized)
        self._index_by_shard = [dict() for _ in range(self.n_shards)]
        self._block_digest: dict = {}       # block -> (shard, digest)
        self._cached_by_shard = [OrderedDict()      # refcount-0 indexed
                                 for _ in range(self.n_shards)]  # blocks, LRU
        self._slot_digests: dict = {}       # slot -> prompt block digests
        self._slot_borrowed: dict = {}      # slot -> {shared page indices}
        self._slot_matched: dict = {}       # slot -> matched prefix tokens
        self._slot_registered: dict = {}    # slot -> pages indexed so far
        self.prefix_hit_requests = 0
        self.prefix_hit_blocks = 0
        self.prefix_hit_tokens = 0
        self.cow_forks = 0
        self.reclaimed_cached_blocks = 0
        # ---- fault containment: poisoned-block quarantine ----
        # quarantined blocks are permanently out of circulation: never on a
        # free list, never cached, never indexed — capacity shrinks by one
        # block each, which later allocations feel as organic pressure
        self._quarantined_by_shard = [set() for _ in range(self.n_shards)]
        # blocks awaiting quarantine: still referenced by a borrower the
        # fork-off couldn't relocate (pool dry); _release routes them into
        # quarantine the moment the last reference drops
        self._quarantine_pending: set = set()
        self.quarantined_blocks = 0          # per-drain tally

    # ---- cross-drain lifecycle ----------------------------------------
    def reset_counters(self) -> None:
        """Zero the per-drain telemetry tallies. The engine persists one
        pool across ``serve()`` drains (so the prefix index survives between
        calls); each drain's counters start fresh here. Quarantined blocks
        stay quarantined — only the drain tally resets."""
        self.prefix_hit_requests = 0
        self.prefix_hit_blocks = 0
        self.prefix_hit_tokens = 0
        self.cow_forks = 0
        self.reclaimed_cached_blocks = 0
        self.quarantined_blocks = 0

    def invalidate_prefix_index(self) -> None:
        """Forget every indexed prefix block. Cached (refcount-0) blocks
        return to the free lists; blocks still referenced by live slots stay
        mapped but are de-indexed, so they rejoin the free list on release
        instead of the cached LRU. Live slots' digest chains are dropped too
        — no block written before this call can ever satisfy a future hit.

        Called on an adaptive MP plan swap: quantized K/V bytes are a
        function of the *plan* (activation scales and cache formats differ),
        so content indexed under the old plan must not be claimed by
        requests admitted under the new one."""
        for blk in list(self._block_digest):
            d, _ = self._block_digest[blk]
            self._deindex(blk)
            if blk in self._cached_by_shard[d]:
                del self._cached_by_shard[d][blk]
                self._free_blocks_by_shard[d].append(blk)
        for idx in self._index_by_shard:
            idx.clear()
        for slot in self._slot_digests:
            self._slot_digests[slot] = []

    # ---- geometry -----------------------------------------------------
    @staticmethod
    def plan_blocks(n_slots: int, max_len: int, block_size: int,
                    n_blocks=None, data_shards: int = 1) -> tuple:
        """Resolve the pool geometry: ``(n_blocks, shard_pages,
        blocks_per_shard)``. The single source of truth shared by the pool
        allocator and :func:`repro.serve.parallel.make_serving_layout`.

        Pages shard over ``data`` only when both the slot axis and the block
        count split evenly; otherwise the pool stays replicated (matching
        the ``kv_blocks`` rule's divisibility fallback) and allocation is
        global with the single trash block 0."""
        max_blocks = -(-max_len // block_size)
        slots_ok = data_shards > 1 and n_slots % data_shards == 0
        if n_blocks is None:
            # worst case: every slot decodes to max_len (same HBM as dense,
            # modulo block rounding); size it tighter to realize the win —
            # see size_n_blocks. Sharded pools give every shard its own
            # trash block so per-shard capacity stays uniform.
            n_blocks = (data_shards * (1 + (n_slots // data_shards) * max_blocks)
                        if slots_ok else 1 + n_slots * max_blocks)
        shard = (slots_ok and n_blocks % data_shards == 0
                 and n_blocks >= 2 * data_shards)
        assert n_blocks >= 2, "need at least the trash block plus one"
        return n_blocks, shard, n_blocks // (data_shards if shard else 1)

    @staticmethod
    def size_n_blocks(profile, n_slots: int, block_size: int, *,
                      percentile: float = 95.0, headroom: float = 1.25,
                      data_shards: int = 1) -> int:
        """Size ``n_blocks`` from a measured request profile instead of the
        worst case: simulate the FCFS live-block trajectory of ``profile``
        (an iterable of ``(prompt_len, max_new_tokens)`` pairs) over
        ``n_slots`` decode rows at one decode step per tick, take the given
        ``percentile`` of the per-tick live-block totals, multiply by
        ``headroom`` (the SLA knob: how much of the tail demand the pool
        must absorb without backpressure), and add the trash block(s).

        The result is clamped to ``[largest single request + trash,
        worst case]`` and rounded up to a multiple of ``data_shards`` so a
        sharded pool splits evenly. Sub-worst-case sizing trades HBM for
        occasional admission backpressure — exactly the dial the paper's
        gained-time-vs-constraint framing prices."""
        profile = [(int(p), int(m)) for p, m in profile]
        if not profile:
            raise ValueError("size_n_blocks needs a non-empty profile")
        bf = lambda n: max(-(-n // block_size), 1)
        max_blocks_req = max(bf(p + max(m - 1, 0)) for p, m in profile)
        worst = n_slots * max(max_blocks_req, 1)
        # FCFS over n_slots rows: request occupies its slot for max(m, 1)
        # ticks; at decode tick t it holds the blocks covering p + t tokens
        free_at = [0] * n_slots
        demand: dict = {}
        for p, m in profile:
            s = min(range(n_slots), key=free_at.__getitem__)
            start, dur = free_at[s], max(m, 1)
            for t in range(dur):
                demand[start + t] = demand.get(start + t, 0) + bf(p + t)
            free_at[s] = start + dur
        live = sorted(demand.values())
        idx = min(int(len(live) * percentile / 100.0), len(live) - 1)
        need = int(np.ceil(live[idx] * headroom))
        need = max(min(need, worst), max_blocks_req)
        n = need + max(data_shards, 1)                     # trash block(s)
        if data_shards > 1:                                # even shard split
            n = -(-n // data_shards) * data_shards
        return n

    # ---- budget / accounting ----
    @property
    def n_free_slots(self) -> int:
        return sum(len(s) for s in self._free_slots_by_shard)

    @property
    def n_free_blocks(self) -> int:
        """Allocatable blocks: truly free plus cached (refcount-0 indexed
        blocks are resident prefix content, reclaimed on demand)."""
        return (sum(len(b) for b in self._free_blocks_by_shard)
                + self.n_cached_blocks)

    @property
    def n_cached_blocks(self) -> int:
        return sum(len(c) for c in self._cached_by_shard)

    @property
    def n_quarantined_blocks(self) -> int:
        return sum(len(q) for q in self._quarantined_by_shard)

    @property
    def blocks_in_use(self) -> int:
        return ((self.n_blocks - self.n_shards - self.n_quarantined_blocks)
                - self.n_free_blocks)

    @property
    def _reserved(self) -> int:
        return sum(self._reserved_by_shard)

    @property
    def allocatable_blocks(self) -> int:
        """Largest single-request reservation the pool can ever satisfy —
        one shard's capacity minus its trash block and any quarantined
        blocks (quarantine permanently shrinks capacity)."""
        return (self.blocks_per_shard - 1
                - min(len(q) for q in self._quarantined_by_shard))

    def _shard_of(self, slot: int) -> int:
        return slot // self.slots_per_shard if self.n_shards > 1 else 0

    def blocks_for(self, n_tokens: int) -> int:
        return max(-(-n_tokens // self.block_size), 1)

    def blocks_for_request(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case blocks a request can touch: the prompt plus one KV
        write per decode step (the last generated token is never written)."""
        return self.blocks_for(prompt_len + max(max_new_tokens - 1, 0))

    # ---- prefix hashing / matching ------------------------------------
    def prefix_digests(self, tokens) -> list:
        """Chained content digests of every *full* block of ``tokens``:
        ``h_j = sha256(h_{j-1} || tokens[j*bs:(j+1)*bs])``. Because each
        digest folds in the whole chain before it, one index hit at block j
        implies blocks 0..j all match — matching is a single walk down the
        chain, no per-block prefix comparison."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        bs = self.block_size
        out, h = [], b""
        for j in range(toks.shape[0] // bs):
            h = hashlib.sha256(h + toks[j * bs:(j + 1) * bs].tobytes()).digest()
            out.append(h)
        return out

    def _match_blocks(self, d: int, digests) -> list:
        """Longest resident chain on shard ``d``: blocks for digests[0..m)."""
        blks = []
        idx = self._index_by_shard[d]
        for h in digests:
            b = idx.get(h)
            if b is None:
                break
            blks.append(b)
        return blks

    def _plan_admission(self, prompt_len: int, max_new_tokens: int,
                        digests=None):
        """Shard-aware admission plan: for every shard with a free slot,
        walk the request's digest chain against that shard's index and
        check the *net* block need (worst case minus matched, plus one for
        the copy-on-write fork a fully-matched prompt's tail chunk needs)
        against the shard's own free + cached - reserved budget. Returns
        ``(shard, matched_blocks, matched_tokens, need)`` for the shard
        with the longest match (free capacity breaks ties), or None when no
        shard can admit — per-shard gating, so one hot shard can't strand
        capacity on the others."""
        total = self.blocks_for_request(prompt_len, max_new_tokens)
        best = None
        for d in range(self.n_shards):
            if not self._free_slots_by_shard[d]:
                continue
            blks = self._match_blocks(d, digests) if digests else []
            m = len(blks)
            matched = m * self.block_size
            cow = 0
            if m and matched >= prompt_len:
                # full-prompt hit: the tail chunk still runs (it produces
                # the first token) and must fork the last shared block
                matched = prompt_len - 1
                cow = 1
            need = total - m + cow
            cached = self._cached_by_shard[d]
            claim_from_cached = sum(1 for b in blks if b in cached)
            avail = (len(self._free_blocks_by_shard[d]) + len(cached)
                     - claim_from_cached - self._reserved_by_shard[d])
            if need > avail:
                continue
            key = (m, avail, -d)
            if best is None or key > best[0]:
                best = (key, (d, blks, matched, need))
        return None if best is None else best[1]

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  digests=None) -> bool:
        return self._plan_admission(prompt_len, max_new_tokens,
                                    digests) is not None

    def matched_tokens(self, slot: int) -> int:
        """Prefix tokens ``slot`` inherited at admission — its prefill
        starts there instead of 0."""
        return self._slot_matched.get(slot, 0)

    # ---- slot lifecycle ----
    def alloc_slot(self, prompt_len: int, max_new_tokens: int,
                   digests=None) -> int:
        """Claim a slot, map any matched prefix blocks into its table
        (refcount + 1 each), and reserve the rest of the request's
        worst-case block budget."""
        total = self.blocks_for_request(prompt_len, max_new_tokens)
        if total > self.allocatable_blocks:
            raise ValueError(
                f"request needs {total} blocks but the pool only has "
                f"{self.allocatable_blocks} allocatable blocks"
                + (" per shard" if self.n_shards > 1 else ""))
        plan = self._plan_admission(prompt_len, max_new_tokens, digests)
        if plan is None:
            raise PoolExhausted("paged cache pool exhausted")
        d, blks, matched, need = plan
        slot = self._free_slots_by_shard[d].pop()
        self._reserved_by_shard[d] += need
        self._slot_reserve[slot] = need
        self._slot_blocks[slot] = []
        self._slot_digests[slot] = list(digests) if digests else []
        self._slot_borrowed[slot] = set()
        self._slot_matched[slot] = matched
        self._slot_registered[slot] = 0
        for j, b in enumerate(blks):
            self._claim(d, b)
            self.block_tables[slot, j] = b
            self._slot_blocks[slot].append(b)
            self._slot_borrowed[slot].add(j)
        if blks:
            self.prefix_hit_requests += 1
            self.prefix_hit_blocks += len(blks)
            self.prefix_hit_tokens += matched
        return slot

    def free_slot(self, slot: int) -> None:
        """Return the slot, drop its block references (refcount-0 indexed
        blocks stay cached for future prefix hits; unindexed blocks rejoin
        the free list), and release any unused reservation."""
        d = self._shard_of(slot)
        assert slot not in self._free_slots_by_shard[d], slot
        for b in reversed(self._slot_blocks.pop(slot, [])):
            self._release(d, b)
        self._reserved_by_shard[d] -= self._slot_reserve.pop(slot, 0)
        for per_slot in (self._slot_digests, self._slot_borrowed,
                         self._slot_matched, self._slot_registered):
            per_slot.pop(slot, None)
        self.block_tables[slot] = -1
        self._free_slots_by_shard[d].append(slot)

    # ---- refcounted block lifecycle -----------------------------------
    def _claim(self, d: int, blk: int) -> None:
        """Take a reference on a resident block (a prefix hit)."""
        self._ref[blk] = self._ref.get(blk, 0) + 1
        self._cached_by_shard[d].pop(blk, None)     # in use again

    def _release(self, d: int, blk: int) -> None:
        self._ref[blk] -= 1
        assert self._ref[blk] >= 0, (blk, self._ref[blk])
        if self._ref[blk] == 0:
            del self._ref[blk]
            if blk in self._quarantine_pending:
                # a poisoned block whose last borrower just let go: it goes
                # straight to quarantine, never back into circulation
                self._quarantine_pending.discard(blk)
                self._quarantine(d, blk)
            elif blk in self._block_digest:
                # indexed content stays resident (LRU reclaim on pressure)
                self._cached_by_shard[d][blk] = None
            else:
                self._free_blocks_by_shard[d].append(blk)

    def _deindex(self, blk: int) -> None:
        d, h = self._block_digest.pop(blk)
        if self._index_by_shard[d].get(h) == blk:
            del self._index_by_shard[d][h]

    def _alloc_block(self, slot: int) -> int:
        d = self._shard_of(slot)
        if self._free_blocks_by_shard[d]:
            blk = self._free_blocks_by_shard[d].pop()
        elif self._cached_by_shard[d]:
            # reclaim the least recently released cached block; de-indexing
            # it truncates any digest chain through it (later links become
            # unreachable, which is safe: a chain hit requires every link)
            blk, _ = self._cached_by_shard[d].popitem(last=False)
            self._deindex(blk)
            self.reclaimed_cached_blocks += 1
        else:
            raise PoolExhausted("paged cache pool out of blocks")
        if self._slot_reserve.get(slot, 0) > 0:
            self._slot_reserve[slot] -= 1
            self._reserved_by_shard[d] -= 1
        self._ref[blk] = 1
        self._slot_blocks[slot].append(blk)
        return blk

    def register_prefix(self, slot: int, upto_tokens: int) -> None:
        """Index ``slot``'s fully-written prompt blocks (logical positions
        ``[0, upto_tokens)``) under their chain digests so later requests
        can match them. Idempotent and incremental: call after each prefill
        chunk with the cumulative prefilled length. First writer wins — a
        digest already indexed (e.g. the block this slot itself borrowed)
        is skipped, keeping exactly one canonical block per chain node.

        Safe to call right after the chunk *dispatches* (before the device
        writes land): any future reader's chunks are dispatched later on
        the same device stream, so they order after this slot's writes."""
        digests = self._slot_digests.get(slot)
        if not digests:
            return
        d = self._shard_of(slot)
        idx = self._index_by_shard[d]
        done = self._slot_registered.get(slot, 0)
        end = min(int(upto_tokens) // self.block_size, len(digests))
        for j in range(done, end):
            h = digests[j]
            blk = int(self.block_tables[slot, j])
            assert blk >= 0, (slot, j)
            if h not in idx:
                idx[h] = blk
                self._block_digest[blk] = (d, h)
        self._slot_registered[slot] = max(done, end)

    # ---- fault containment: quarantine + reconcile --------------------
    def _quarantine(self, d: int, blk: int) -> None:
        """Retire ``blk`` from circulation permanently. Caller guarantees
        the refcount is zero (no table maps it)."""
        if blk in self._block_digest:
            self._deindex(blk)
        self._quarantined_by_shard[d].add(blk)
        self.quarantined_blocks += 1

    def _alloc_block_unreserved(self, d: int):
        """Pop a block from shard ``d`` without charging any slot's
        reservation — the quarantine fork-off path: the copy a borrower
        needs was never part of its admission-time budget. Returns None
        (instead of raising) when the shard is dry: the caller degrades
        gracefully. May leave ``reserved > free + cached``; a later
        ``_alloc_block`` then raises, which the engine contains as an
        allocation fault — quarantine pressure surfaces as backpressure,
        never as a crash."""
        if self._free_blocks_by_shard[d]:
            blk = self._free_blocks_by_shard[d].pop()
        elif self._cached_by_shard[d]:
            blk, _ = self._cached_by_shard[d].popitem(last=False)
            self._deindex(blk)
            self.reclaimed_cached_blocks += 1
        else:
            return None
        self._ref[blk] = 1
        return blk

    def _fork_off(self, slot: int, page: int) -> bool:
        """Copy ``slot``'s (borrowed) ``page`` onto a private block so the
        quarantined source loses this reader. The copy may itself carry
        poisoned bytes — if it does, the borrower's own tripwire fires and
        containment recurses; what quarantine guarantees is that the *block*
        can never be re-allocated or prefix-matched again. False when the
        pool is dry (the borrower keeps the pending-quarantine page)."""
        d = self._shard_of(slot)
        src = int(self.block_tables[slot, page])
        dst = self._alloc_block_unreserved(d)
        if dst is None:
            return False
        self._slot_blocks[slot].append(dst)
        self._copy_block_device(src, dst)
        self.block_tables[slot, page] = dst
        self._slot_blocks[slot].remove(src)
        self._release(d, src)
        borrowed = self._slot_borrowed.get(slot)
        if borrowed is not None:
            borrowed.discard(page)
        return True

    def quarantine_slot(self, slot: int) -> int:
        """Poisoned-page containment for a faulted slot: every block its
        table maps is (1) de-indexed — no future prefix hit can walk through
        it; (2) stripped of other live borrowers via device-side fork-off
        copies; (3) dropped from this slot's table and retired to the
        quarantine set, from which no allocation path (free list, cached
        LRU) can ever produce it again. Conservative by design: detection
        is a non-finite *logit* row, which does not localize the poisoned
        page, so the whole mapping is suspect. Returns the number of blocks
        newly quarantined (borrowed blocks whose fork-off failed quarantine
        later, on their last release). Call before ``free_slot``."""
        d = self._shard_of(slot)
        before = self.quarantined_blocks
        borrowed = self._slot_borrowed.get(slot, set())
        for page in range(self.max_blocks):
            blk = int(self.block_tables[slot, page])
            if blk < 0:
                continue
            if blk in self._block_digest:
                self._deindex(blk)
            self._quarantine_pending.add(blk)
            for t in range(self.n_slots):
                if t == slot:
                    continue
                for p in np.nonzero(self.block_tables[t] == blk)[0]:
                    self._fork_off(t, int(p))
            self.block_tables[slot, page] = -1
            self._slot_blocks[slot].remove(blk)
            borrowed.discard(page)
            self._release(d, blk)
        return self.quarantined_blocks - before

    def poison_block(self, blk: int) -> None:
        """Overwrite physical block ``blk`` with NaN in every block-major
        floating-point cache leaf — the fault injector's NaN-page primitive
        (device-side, one jitted scatter). Test harness only."""
        # n_blocks is baked into the closure's leaf filter, so pools with
        # different geometries must not share a compiled poisoner
        key = (id(self.model), id(self.layout), "poison", self.n_blocks)
        entry = _COW_JIT_CACHE.get(key)
        if entry is None:
            nb = self.n_blocks

            def _poison(caches, b):
                def upd(x):
                    if (x.ndim >= 2 and x.shape[0] == nb
                            and jnp.issubdtype(x.dtype, jnp.floating)):
                        return x.at[b].set(jnp.nan)
                    return x
                return jax.tree.map(upd, caches)

            kw = {}
            if self.layout is not None:
                kw["out_shardings"] = jax.tree.map(lambda x: x.sharding,
                                                   self.caches)
            entry = (self.model, self.layout, jax.jit(_poison, **kw))
            _COW_JIT_CACHE[key] = entry
        self.caches = entry[2](self.caches, jnp.asarray(blk, jnp.int32))

    def check_consistency(self) -> dict:
        """Cross-check the allocator's books against the block tables (the
        single source of truth for what is mapped): table multiset ==
        refcounts, and free/cached/quarantined sets disjoint from mapped
        blocks. Returns a report dict with ``ok`` plus the mismatches."""
        from collections import Counter
        mat = Counter(int(b) for row in self.block_tables
                      for b in row if b >= 0)
        free = set()
        for lst in self._free_blocks_by_shard:
            free.update(lst)
        cached = set()
        for c in self._cached_by_shard:
            cached.update(c)
        quarantined = set()
        for q in self._quarantined_by_shard:
            quarantined.update(q)
        mapped = set(mat)
        report = {
            "tables_vs_ref": mat == Counter(self._ref),
            "free_mapped": sorted(free & mapped),
            "cached_mapped": sorted(cached & mapped),
            "quarantined_mapped": sorted(quarantined & mapped),
            "quarantined_free": sorted(quarantined & (free | cached)),
        }
        report["ok"] = (report["tables_vs_ref"]
                        and not report["free_mapped"]
                        and not report["cached_mapped"]
                        and not report["quarantined_mapped"]
                        and not report["quarantined_free"])
        return report

    def reconcile(self) -> dict:
        """Repair the allocator's books after an error bail-out: recompute
        every refcount from the block tables and route orphaned blocks
        (referenced by no table) back to the cached LRU / free list — or to
        quarantine if poisoned. Run by the engine on the consumer-error
        shutdown path, after every slot has been released, so a drain that
        re-raises a callback error still leaves the persistent pool in a
        state the next drain can safely reuse. Returns what changed."""
        from collections import Counter
        mat = Counter(int(b) for row in self.block_tables
                      for b in row if b >= 0)
        fixed = 0
        for blk, want in mat.items():
            if self._ref.get(blk) != want:
                self._ref[blk] = want
                fixed += 1
        orphans = 0
        for blk in [b for b in self._ref if b not in mat]:
            del self._ref[blk]
            d = self._shard_of_block(blk)
            self._cached_by_shard[d].pop(blk, None)
            if blk in self._free_blocks_by_shard[d]:
                continue
            if blk in self._quarantine_pending:
                self._quarantine_pending.discard(blk)
                self._quarantine(d, blk)
            elif blk in self._block_digest:
                self._cached_by_shard[d][blk] = None
            else:
                self._free_blocks_by_shard[d].append(blk)
            orphans += 1
        return {"ref_fixed": fixed, "orphans_rerouted": orphans,
                "consistent": self.check_consistency()["ok"]}

    def _shard_of_block(self, blk: int) -> int:
        return blk // self.blocks_per_shard if self.n_shards > 1 else 0

    def _cow_fork(self, slot: int, page: int) -> None:
        """Copy-on-write: ``slot`` is about to write into shared ``page`` —
        duplicate the block on device, repoint the table at the private
        copy, and drop the shared reference. The parent block (and the
        chain through it) is never mutated."""
        d = self._shard_of(slot)
        src = int(self.block_tables[slot, page])
        dst = self._alloc_block(slot)       # before release: the fork must
        self._copy_block_device(src, dst)   # never reclaim its own source
        self.block_tables[slot, page] = dst
        self._slot_blocks[slot].remove(src)
        self._release(d, src)
        self._slot_borrowed[slot].discard(page)
        self.cow_forks += 1

    def _copy_block_device(self, src: int, dst: int) -> None:
        key = (id(self.model), id(self.layout))
        entry = _COW_JIT_CACHE.get(key)
        if entry is None:
            kw = {}
            if self.layout is not None:
                kw["out_shardings"] = jax.tree.map(lambda x: x.sharding,
                                                   self.caches)
            entry = (self.model, self.layout,
                     jax.jit(self.model.paged_copy_block, **kw))
            _COW_JIT_CACHE[key] = entry
        self.caches = entry[2](self.caches, jnp.asarray(src, jnp.int32),
                               jnp.asarray(dst, jnp.int32))

    def ensure_block(self, slot: int, pos: int) -> None:
        """Alloc-on-demand: materialize the page for write position ``pos``
        when decode crosses a block boundary. Covered by the admission-time
        reservation, so it cannot fail for an admitted request."""
        page, off = divmod(int(pos), self.block_size)
        if off == 0 and self.block_tables[slot, page] < 0:
            self.block_tables[slot, page] = self._alloc_block(slot)
        else:
            # decode never writes a shared page: matched prefixes end
            # before the first decode position, and a fully-matched
            # prompt's last block was forked by the tail prefill chunk
            borrowed = self._slot_borrowed.get(slot)
            assert not borrowed or page not in borrowed, (slot, page)

    def ensure_range(self, slot: int, start: int, end: int) -> None:
        """Materialize every page covering logical positions [start, end) —
        chunked prefill's incremental reservation: blocks appear chunk by
        chunk (each drawing on the admission-time reservation) instead of
        the whole prompt's worth at once, so blocks a later chunk will fill
        stay in the free pool until that chunk actually runs. A page that
        is present but *borrowed* (shared prefix block) is copy-on-write
        forked before the chunk writes into it — this only happens for the
        tail chunk of a fully-matched prompt."""
        assert 0 <= start < end, (start, end)
        borrowed = self._slot_borrowed.get(slot)
        last = -(-int(end) // self.block_size)
        for page in range(int(start) // self.block_size, last):
            if self.block_tables[slot, page] < 0:
                self.block_tables[slot, page] = self._alloc_block(slot)
            elif borrowed and page in borrowed:
                self._cow_fork(slot, page)

    # ---- device-side contents ----
    def insert(self, slot: int, request_cache: dict, prompt_len: int) -> None:
        """Allocate the prompt's blocks and scatter a batch=1 dense prefill
        cache into them (the prefill cache must be sized to exactly
        ``blocks_for(prompt_len) * block_size``).

        Not on the engine's serving path since the chunked-prefill rewrite
        (prefill now writes blocks in place via ``paged_write_chunk``); kept
        as the generic externally-prefilled-cache injection API and covered
        by the pool tests."""
        nb = self.blocks_for(prompt_len)
        ids = [self._alloc_block(slot) for _ in range(nb)]
        self.block_tables[slot, :nb] = ids
        self.caches = self._insert_fn(self.caches, request_cache,
                                      jnp.asarray(ids, jnp.int32),
                                      jnp.asarray(slot, jnp.int32))

    def block_tables_device(self) -> jax.Array:
        # hand jax a private copy: on CPU, jnp.asarray(host_array) may be
        # zero-copy, and the pool mutates block_tables in place
        # (ensure_block/ensure_range/free_slot) — under the pipelined engine
        # a dispatched step may still be reading the aliased buffer when the
        # next tick's allocation rewrites it
        return jnp.asarray(self.block_tables.copy())
