"""Fault-tolerant serving: the injection harness, engine containment
(bounded retry through the bit-exact resume path, poisoned-page quarantine,
fused->gather degradation), the tau-anchored numerical guardrail, pool book
reconciliation, and the corrupted-bundle registry fall-through.

The load-bearing bar throughout: a drain with injected faults always
terminates with a result for every request, every fault-unaffected request's
tokens are bit-identical to a fault-free run, and a retried request that
completes is bit-identical too (resume is bit-exact). ``failed`` requests
keep the last-known-good prefix."""
import os

import jax
import numpy as np
import pytest

from repro.core.mpconfig import MPPlan
from repro.models.registry import get_model
from repro.serve import (AdaptiveMPController, ContinuousBatchingEngine,
                         FaultInjector, FaultSpec, NumericalGuardrail,
                         PagedCachePool, Request)

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:          # pragma: no cover - exercised on minimal images
    HAS_HYPOTHESIS = False

MP_ASSIGNMENT = {
    "layers/0/attn/q_proj": "fp8_e4m3",
    "layers/1/mlp/down_proj": "fp8_e4m3",
    "lm_head": "fp8_e4m3",
}


@pytest.fixture(scope="module")
def model():
    return get_model("llama3_1b", smoke=True)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(42)
    # two shared-prefix pairs so prefix caching + COW sharing is live in
    # every engine-level fault test
    fam = rng.integers(0, 500, size=8).astype(np.int32)
    out = [np.concatenate([fam, rng.integers(0, 500, 4).astype(np.int32)])
           for _ in range(2)]
    out += [rng.integers(0, 500, size=12).astype(np.int32) for _ in range(2)]
    return out


def _requests(prompts, max_new=6, **kw):
    return [Request(rid=i, tokens=p, max_new_tokens=max_new, **kw)
            for i, p in enumerate(prompts)]


@pytest.fixture(scope="module")
def reference(model, params, prompts):
    """Fault-free continuous-batching tokens (the bit-exactness bar)."""
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32)
    summ = eng.serve(params, _requests(prompts))
    assert all(r.status == "ok" for r in summ.results.values())
    return {i: np.asarray(r.tokens) for i, r in summ.results.items()}


def _assert_contained(summ, ref, *, allow=("ok", "retried", "failed")):
    """Every request has a terminal result; ok/retried are bit-identical to
    the fault-free run; failed keep a bit-exact last-known-good prefix."""
    assert set(summ.results) == set(ref)
    for i, r in summ.results.items():
        assert r.status in allow, (i, r.status)
        if r.status in ("ok", "retried"):
            np.testing.assert_array_equal(r.tokens, ref[i])
        else:
            np.testing.assert_array_equal(
                np.asarray(r.tokens), ref[i][:len(r.tokens)])


# ---------------------------------------------------------------------------
# the fault matrix: every fault class x sync/async, paged + prefix sharing
# ---------------------------------------------------------------------------

MATRIX = [
    ("step_exception", dict(step=2, phase="decode")),
    ("step_exception", dict(step=0, phase="prefill")),
    ("nan_page", dict(step=2, slot=0, page=0)),
    ("nan_logits", dict(step=2, slot=1)),
    ("alloc_failure", dict(step=1, slot=2)),
    ("consumer_error", dict(step=2, slot=3)),
    ("consumer_stall", dict(step=2, hang_s=0.001)),
    ("hung_step", dict(step=2, phase="decode", hang_s=0.001)),
]


@pytest.mark.parametrize("sync", [False, True])
@pytest.mark.parametrize("kind,kw", MATRIX,
                         ids=[f"{k}-{kw.get('phase', 'any')}"
                              for k, kw in MATRIX])
def test_fault_matrix(model, params, prompts, reference, kind, kw, sync):
    inj = FaultInjector([FaultSpec(kind=kind, **kw)])
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32, faults=inj)
    summ = eng.serve(params, _requests(prompts), sync=sync)
    _assert_contained(summ, reference)
    f = summ.counters["faults"]
    assert f["injected"].get(kind) == 1, f
    if kind in ("nan_page", "nan_logits"):
        # the tripwire caught the poison and the pages left circulation
        assert f["seen"].get("nonfinite_logits", 0) >= 1
        assert f["quarantined_blocks"] >= 1
        assert any(r.status == "retried" for r in summ.results.values())
    if kind == "consumer_error":
        # contained per-request, no retry (the tokens already streamed)
        assert sum(1 for r in summ.results.values()
                   if r.status == "failed") == 1
    if kind in ("consumer_stall", "hung_step"):
        # pure latency faults: nothing degrades to failed/retried
        assert all(r.status == "ok" for r in summ.results.values())
    # pool books settle after every containment path
    pool = eng._pool
    assert pool.check_consistency()["ok"]
    assert pool.blocks_in_use == 0 and pool._reserved == 0


def test_retry_budget_exhausted_fails(model, params, prompts, reference):
    """max_retries=0: the poisoned request retires ``failed`` with its
    last-known-good prefix; everyone else is untouched."""
    inj = FaultInjector([FaultSpec("nan_logits", step=3, slot=1)])
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32, faults=inj,
                                   max_retries=0)
    summ = eng.serve(params, _requests(prompts))
    _assert_contained(summ, reference)
    failed = [r for r in summ.results.values() if r.status == "failed"]
    assert len(failed) == 1 and len(failed[0].tokens) < len(
        reference[failed[0].rid])
    assert summ.counters["faults"]["failed"] == 1


def test_repeated_kernel_faults_degrade_to_gather(model, params, prompts,
                                                  reference):
    """Past kernel_fault_limit step faults the engine swaps fused paged
    attention for the gather path mid-drain — a dispatch switch, and the
    pinned fused/gather parity keeps tokens bit-identical."""
    inj = FaultInjector([FaultSpec("step_exception", step=1),
                         FaultSpec("step_exception", step=3)])
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32, faults=inj,
                                   max_retries=3, kernel_fault_limit=2)
    summ = eng.serve(params, _requests(prompts))
    _assert_contained(summ, reference)
    f = summ.counters["faults"]
    assert f["kernel_faults"] == 2 and f["degraded_paged_attn"]
    assert eng.paged_attn == "gather"
    assert all(r.status == "retried" for r in summ.results.values())


def test_impossible_after_quarantine_fails_gracefully(model, params):
    """Quarantine shrinks capacity below a previously-admissible request's
    worst-case need: it retires ``failed`` instead of crashing the drain."""
    prompt = np.random.default_rng(7).integers(0, 500, 12).astype(np.int32)
    inj = FaultInjector([FaultSpec("nan_logits", step=1, slot=0)])
    eng = ContinuousBatchingEngine(model, n_slots=2, max_len=32, faults=inj,
                                   block_size=4, n_blocks=7, max_retries=2)
    summ = eng.serve(params, [Request(rid=0, tokens=prompt,
                                      max_new_tokens=8)])
    r = summ.results[0]
    assert r.status == "failed"
    f = summ.counters["faults"]
    assert f["quarantined_blocks"] >= 4
    assert f["seen"].get("impossible_request", 0) == 1
    # the pool stays consistent with pages permanently out of circulation
    pool = eng._pool
    assert pool.check_consistency()["ok"]
    assert pool.n_quarantined_blocks == f["quarantined_blocks"]
    assert pool.allocatable_blocks == 6 - pool.n_quarantined_blocks


@pytest.mark.parametrize("method", ["ensure_range", "ensure_block"])
def test_device_error_in_allocation_ends_the_drain(model, params, prompts,
                                                   monkeypatch, method):
    """Only pool exhaustion is contained as an allocation failure. A device
    error there — an OOM, a failed compile of the COW block copy — is a
    ``jax.errors.JaxRuntimeError``, itself a ``RuntimeError``, and must end
    the drain instead of retiring requests ``failed``."""
    def device_error(self, *a, **kw):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(PagedCachePool, method, device_error)
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.serve(params, _requests(prompts))


# ---------------------------------------------------------------------------
# pool-level quarantine mechanics
# ---------------------------------------------------------------------------


def test_quarantine_slot_removes_blocks_for_good(model):
    pool = PagedCachePool(model, n_slots=2, max_len=32, block_size=4,
                          n_blocks=12)
    p = np.random.default_rng(0).integers(0, 500, 12).astype(np.int32)
    s = pool.alloc_slot(12, 4, digests=pool.prefix_digests(p))
    pool.ensure_range(s, 0, 12)
    pool.register_prefix(s, 12)
    owned = [int(b) for b in pool.block_tables[s] if b >= 0]
    n = pool.quarantine_slot(s)
    pool.free_slot(s)
    assert n == len(owned) == pool.n_quarantined_blocks
    assert pool.quarantined_blocks == n
    assert pool.check_consistency()["ok"]
    # quarantined pages never reappear: not free, not cached, not indexed
    assert not set(owned) & set(pool._free_blocks_by_shard[0])
    assert not set(owned) & set(pool._cached_by_shard[0])
    assert pool.allocatable_blocks == (pool.blocks_per_shard - 1 - n)
    # a prefix that previously hit now misses (the chain was de-indexed)
    s2 = pool.alloc_slot(12, 4, digests=pool.prefix_digests(p))
    assert pool.matched_tokens(s2) == 0
    pool.ensure_range(s2, 0, 12)
    assert not set(owned) & {int(b) for b in pool.block_tables[s2] if b >= 0}
    pool.free_slot(s2)
    assert pool.blocks_in_use == 0 and pool._reserved == 0


def test_quarantine_forks_live_borrowers(model):
    """A borrower of a shared (prefix-hit) block keeps decoding: quarantine
    COW-forks the page away before pulling it from circulation."""
    pool = PagedCachePool(model, n_slots=2, max_len=32, block_size=4,
                          n_blocks=16)
    p = np.random.default_rng(1).integers(0, 500, 12).astype(np.int32)
    s0 = pool.alloc_slot(12, 4, digests=pool.prefix_digests(p))
    pool.ensure_range(s0, 0, 12)
    pool.register_prefix(s0, 12)
    s1 = pool.alloc_slot(12, 4, digests=pool.prefix_digests(p))
    hit = pool.matched_tokens(s1)
    assert hit >= 8                             # >= two full pages borrowed
    pool.ensure_range(s1, hit, 12)              # COW-forks any partial page
    shared = (set(int(b) for b in pool.block_tables[s0] if b >= 0)
              & set(int(b) for b in pool.block_tables[s1] if b >= 0))
    assert len(shared) >= 2                     # fully-shared prefix pages
    n = pool.quarantine_slot(s0)
    pool.free_slot(s0)
    assert n >= 3
    after = [int(b) for b in pool.block_tables[s1] if b >= 0]
    assert not set(after) & shared              # every page forked away
    assert pool.check_consistency()["ok"]
    # the borrower still decodes into its (now private) pages
    for pos in range(12, 15):
        pool.ensure_block(s1, pos)
    pool.free_slot(s1)
    assert pool.blocks_in_use == 0 and pool._reserved == 0


def test_poison_block_is_device_visible(model):
    pool = PagedCachePool(model, n_slots=1, max_len=16, block_size=4,
                          n_blocks=6)
    s = pool.alloc_slot(8, 2)
    pool.ensure_range(s, 0, 8)
    blk = int(pool.block_tables[s][1])
    pool.poison_block(blk)
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(pool.caches)
    hit = [x for x in leaves
           if x.ndim >= 2 and x.shape[0] == pool.n_blocks
           and jnp.issubdtype(x.dtype, jnp.floating)]
    assert hit
    for x in hit:
        host = np.asarray(x[blk], np.float32)
        assert np.isnan(host).all()
        other = int(pool.block_tables[s][0])
        assert np.isfinite(np.asarray(x[other], np.float32)).all()


def test_reconcile_settles_cooked_books(model):
    pool = PagedCachePool(model, n_slots=2, max_len=32, block_size=4,
                          n_blocks=12)
    s = pool.alloc_slot(12, 2)
    pool.ensure_range(s, 0, 12)
    blk = int(pool.block_tables[s][0])
    pool._ref[blk] += 2                         # cook the refcount
    orphan = pool._free_blocks_by_shard[0].pop()
    pool._ref[orphan] = 1                       # strand a block
    assert not pool.check_consistency()["ok"]
    rep = pool.reconcile()
    assert rep["ref_fixed"] >= 1 and rep["orphans_rerouted"] >= 1
    assert rep["consistent"] and pool.check_consistency()["ok"]
    pool.free_slot(s)
    assert pool.blocks_in_use == 0


# ---------------------------------------------------------------------------
# injector construction
# ---------------------------------------------------------------------------


def test_injector_parse_and_random():
    inj = FaultInjector.parse(
        "nan_page@step=3,slot=0,page=1;alloc_failure@step=5,slot=2;"
        "hung_step@step=1,phase=prefill,hang_s=0.5")
    kinds = [s.kind for s in inj.specs]
    assert kinds == ["nan_page", "alloc_failure", "hung_step"]
    assert inj.specs[0].page == 1 and inj.specs[1].slot == 2
    assert inj.specs[2].phase == "prefill"
    assert inj.specs[2].hang_s == pytest.approx(0.5)
    with pytest.raises(ValueError):
        FaultInjector.parse("bogus_kind@step=1")
    with pytest.raises(ValueError):
        FaultInjector.parse("")
    a = FaultInjector.random(11, 6, max_step=10)
    b = FaultInjector.random(11, 6, max_step=10)
    assert [vars(x) for x in a.specs] == [vars(y) for y in b.specs]
    assert [vars(x) for x in FaultInjector.random(12, 6).specs] != \
        [vars(y) for y in a.specs]


def test_injector_hooks_fire_once_and_respect_clock():
    inj = FaultInjector([FaultSpec("step_exception", step=4),
                         FaultSpec("alloc_failure", step=2, slot=1)])
    inj.tick(0)
    assert inj.on_step("decode") is None        # not armed yet
    inj.on_alloc(1)
    inj.tick(3)
    inj.on_alloc(0)                             # wrong slot: no fire
    with pytest.raises(Exception, match="allocation failure"):
        inj.on_alloc(1)
    inj.tick(5)
    with pytest.raises(Exception, match="step exception"):
        inj.on_step("decode")
    assert inj.on_step("decode") is None        # fired exactly once
    assert inj.exhausted and inj.fired == {"step_exception": 1,
                                           "alloc_failure": 1}


# ---------------------------------------------------------------------------
# tau-anchored numerical guardrail
# ---------------------------------------------------------------------------


def _plan(assignment, budget, tau=0.01):
    return MPPlan(assignment=dict(assignment), groups=[list(assignment)],
                  objective="ET", tau=tau, budget=budget,
                  predicted_loss_mse=budget, predicted_gain=1.0)


def test_guardrail_unit_semantics():
    g = NumericalGuardrail(every=4, margin=2.0, max_breaches=2)
    assert not g.observe_mse(0, 1.0, None)      # no budget: record only
    assert g.checks == 1 and g.last_mse == 1.0
    assert not g.observe_mse(4, float("nan"), 1e-6)   # NaN never breaches
    assert g.breaches == 0
    assert not g.observe_mse(8, 1.0, 1e-6)      # breach 1 of 2
    assert g.observe_mse(12, 1.0, 1e-6)         # breach 2: restore now
    assert g.restored_at == 12
    assert not g.observe_mse(16, 1.0, 1e-6)     # restores only once
    assert g.budget_for(_plan(MP_ASSIGNMENT, 0.5)) == pytest.approx(0.5)
    assert g.budget_for(object()) is None
    explicit = NumericalGuardrail(budget=0.25)
    assert explicit.budget_for(_plan(MP_ASSIGNMENT, 0.5)) == \
        pytest.approx(0.25)
    with pytest.raises(ValueError):
        NumericalGuardrail(every=0)


def test_force_restore_bypasses_dwell():
    class _Stub:
        def solve(self, tau, objective):
            return _plan(MP_ASSIGNMENT if tau > 0.01 else {}, 1e-4, tau)

    c = AdaptiveMPController(bundle=_Stub(), taus=[0.01, 0.04],
                             every=4, dwell=100)
    c.level = 1
    plan = c.force_restore(7)
    assert c.level == 0 and plan.tau == pytest.approx(0.01)
    assert c.guardrail_restores == 1 and c.restores == 1
    assert c.history[-1][0] == 7
    n_hist = len(c.history)
    c.force_restore(8)                          # idempotent at level 0
    assert c.level == 0 and c.guardrail_restores == 2
    assert len(c.history) == n_hist


def test_guardrail_breach_restores_mid_drain(model, params, prompts,
                                             reference):
    """A plan whose solved budget lies about its real loss-MSE trips the
    shadow check; the engine force-restores to the base plan mid-drain and
    requests admitted after the restore match the base-plan reference."""
    lying = _plan(MP_ASSIGNMENT, budget=1e-14, tau=1e-7)
    grail = NumericalGuardrail(every=2, margin=2.0)
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32, mp=lying,
                                   guardrail=grail)
    summ = eng.serve(params, _requests(prompts))
    g = summ.counters["guardrail"]
    assert g["breaches"] >= 1 and g["restored_at"] is not None
    assert g["swaps"] and g["swaps"][0]["mse"] > g["swaps"][0]["budget"]
    assert eng.mp is None                       # restored to the base plan
    # post-restore drain on the same engine is bit-identical to fault-free
    summ2 = eng.serve(params, _requests(prompts))
    for i, r in summ2.results.items():
        np.testing.assert_array_equal(r.tokens, reference[i])
    # and the restored engine stops paying for shadow steps
    assert summ2.counters["guardrail"]["checks"] == g["checks"]


def test_guardrail_controller_restore(model, params, prompts):
    """With an adaptive controller attached, a breach routes through
    force_restore: the ladder jumps to level 0 regardless of dwell."""
    class _Stub:
        def solve(self, tau, objective):
            return _plan({} if tau <= 0.01 else MP_ASSIGNMENT,
                         budget=1e-14 if tau > 0.01 else 1e-4, tau=tau)

    ctrl = AdaptiveMPController(bundle=_Stub(), taus=[0.01, 0.04],
                                every=1000, dwell=1000)
    ctrl.level = 1                              # start on the lying plan
    grail = NumericalGuardrail(every=2, margin=2.0)
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32,
                                   adaptive=ctrl, guardrail=grail)
    summ = eng.serve(params, _requests(prompts))
    assert ctrl.level == 0 and ctrl.guardrail_restores == 1
    assert summ.counters["guardrail"]["breaches"] >= 1
    assert all(r.status == "ok" for r in summ.results.values())


def test_honest_plan_never_breaches(model, params, prompts):
    honest = _plan(MP_ASSIGNMENT, budget=1e6)
    grail = NumericalGuardrail(every=3)
    eng = ContinuousBatchingEngine(model, n_slots=4, max_len=32, mp=honest,
                                   guardrail=grail)
    eng.serve(params, _requests(prompts))
    assert grail.checks >= 1 and grail.breaches == 0
    assert grail.last_mse is not None and np.isfinite(grail.last_mse)


# ---------------------------------------------------------------------------
# property test: random fault schedules x random request mixes
# ---------------------------------------------------------------------------


def _check_random_faults(model, params, seed):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 500, size=int(rng.integers(6, 14)))
               .astype(np.int32) for _ in range(int(rng.integers(3, 6)))]
    reqs = [Request(rid=i, tokens=p,
                    max_new_tokens=int(rng.integers(2, 7)),
                    arrival=int(rng.integers(0, 6)))
            for i, p in enumerate(prompts)]
    clean = ContinuousBatchingEngine(model, n_slots=3, max_len=32).serve(
        params, list(reqs))
    ref = {i: np.asarray(r.tokens) for i, r in clean.results.items()}
    inj = FaultInjector.random(seed, int(rng.integers(1, 5)),
                               max_step=12, n_slots=3, max_pages=3)
    for sp in inj.specs:
        sp.hang_s = 0.001
    eng = ContinuousBatchingEngine(model, n_slots=3, max_len=32, faults=inj,
                                   max_retries=2)
    summ = eng.serve(params, list(reqs))
    _assert_contained(summ, ref)
    pool = eng._pool
    assert pool.check_consistency()["ok"]
    assert pool.blocks_in_use == 0 and pool._reserved == 0


def test_random_fault_schedules_fixed_seeds(model, params):
    for seed in (0, 1, 2, 3, 4, 5):
        _check_random_faults(model, params, seed)


if HAS_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_random_fault_schedules(seed):
        m = get_model("llama3_1b", smoke=True)
        _check_random_faults(m, m.init(jax.random.key(0)), seed)
