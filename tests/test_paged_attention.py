"""Fused paged-attention decode kernel vs the gather reference.

The masking invariant under test: the kernel must never *use* a key past a
row's logical length — dead block-table entries (unallocated -1 or stale ids
left by freed slots) and the garbage tail of the last live block must not
leak into the output. Stale-referenced blocks are poisoned with huge finite
garbage for cross-path comparisons (and with NaN for the kernel-only
never-fetched test), so any out-of-length read that survives masking throws
the comparison far outside tolerance.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import (copies_in_loop, heads_per_step,
                                           kernel_steps, pages_per_step,
                                           paged_decode_attention)
from repro.nn import layers as L
from repro.quant.qops import QuantContext

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:          # pragma: no cover - exercised on minimal images
    HAS_HYPOTHESIS = False


def _random_paged_case(seed, *, B, n_pages, bs, dtype, vacant_row=True,
                       stale_entries=True, lengths=()):
    """Cache + block tables with every hazard the pool can produce: rows of
    different lengths, unallocated (-1) entries, stale entries pointing at
    NaN-poisoned blocks, and a vacant row (all -1, length 1 — the engine's
    garbage-row shape, which must read only the trash block 0). ``lengths``
    fixes the first rows' lengths."""
    rng = np.random.default_rng(seed)
    live_budget = B * n_pages
    n_blocks = 1 + live_budget + 4          # trash + live + 4 poison blocks
    lengths = np.concatenate([
        np.asarray(lengths, np.int32),
        rng.integers(1, n_pages * bs + 1, size=B - len(lengths))
    ]).astype(np.int32)
    if vacant_row:
        lengths[-1] = 1
    perm = rng.permutation(np.arange(1, 1 + live_budget))
    poison = np.arange(1 + live_budget, n_blocks)
    tables = np.full((B, n_pages), -1, np.int32)
    c = 0
    for b in range(B):
        if vacant_row and b == B - 1:
            continue                         # vacant: all entries stay -1
        for pg in range(-(-int(lengths[b]) // bs)):
            tables[b, pg] = perm[c]
            c += 1
        if stale_entries:                    # dead entries may be stale ids
            for pg in range(-(-int(lengths[b]) // bs), n_pages):
                if rng.random() < 0.5:
                    tables[b, pg] = rng.choice(poison)
    return n_blocks, jnp.asarray(tables), jnp.asarray(lengths), poison, rng


POISON = 224.0   # huge-but-finite garbage, inside the fp8_e4m3 range: the
# gather reference multiplies exactly-zero probs into gathered stale blocks
# (0 * NaN would be NaN there), so cross-path comparisons need finite poison;
# test_kernel_ignores_nan_in_unreferenced_blocks asserts the kernel's
# stronger never-fetches-them property with real NaN.


def _fill(rng, shape, dtype, poison_blocks, value=POISON):
    x = rng.normal(size=shape).astype(np.float32)
    if len(poison_blocks):
        x[np.asarray(poison_blocks, np.int64)] = value
    return jnp.asarray(x).astype(dtype)


# Kernel geometries: ``P = pages_per_step(bs, n_pages)`` pages per step.
# "pages": one group (P = n_pages). "groups": 20 pages of 8 in groups of 16,
# the last partial; rows of length 0, exactly one group (128), one token
# past it (129) and the whole table, so a window of 7 skips whole groups.
# "loop": the same with 128-lane pages, which the kernel copies itself in a
# loop over the live groups (narrower pages come through the grid).
# "heads": Hkv * G over 128 lanes, so each KV head gets its own grid cell.
GEOMETRY = {
    "pages": dict(B=3, Hkv=2, G=2, Dk=32, n_pages=5, bs=4, lengths=()),
    "groups": dict(B=6, Hkv=2, G=2, Dk=32, n_pages=20, bs=8,
                   lengths=(0, 128, 129, 160)),
    "loop": dict(B=6, Hkv=2, G=2, Dk=128, n_pages=20, bs=8,
                 lengths=(0, 128, 129, 160)),
    "heads": dict(B=3, Hkv=2, G=72, Dk=16, n_pages=5, bs=8, lengths=()),
}


def test_kernel_steps_at_chat_shapes():
    """The benchmark's chat cell: 32 rows, 2 KV heads of 128 in one grid
    cell, a table of 256 pages of 16, about 1,245 live tokens a row. Its
    128-lane pages are copied in a loop over the live groups of 8 pages:
    one step per live group and phase, far under 2,048 per layer, each
    copying; each live page is copied once for K and once for V. Pages of
    64 lanes come through a grid of 32 rows x 2 phases x 32 groups."""
    assert pages_per_step(16, 256) == 8 and heads_per_step(2, 8) == 2
    assert copies_in_loop([128]) and not copies_in_loop([128, 64])
    # the geometries above reach a partial last group and heads in the grid
    g = GEOMETRY["groups"]
    assert pages_per_step(g["bs"], g["n_pages"]) == 16 and g["n_pages"] == 20
    assert heads_per_step(2, GEOMETRY["heads"]["G"]) == 1
    assert heads_per_step(2, GEOMETRY["pages"]["G"]) == 2
    lengths = np.random.default_rng(0).integers(64, 2432, 32)
    groups = -(-lengths // 128)
    steps, copy_steps, pages = kernel_steps(lengths, block_size=16,
                                            n_pages=256, widths=[128])
    assert steps == copy_steps == 2 * groups.sum() <= 2048
    assert pages == (-(-lengths // 16)).sum()
    assert kernel_steps(lengths, block_size=16, n_pages=256,
                        widths=[64]) == (2048, copy_steps, pages)
    # a row of length 0 copies nothing; a window skips whole groups
    assert kernel_steps([0], block_size=16, n_pages=256,
                        widths=[128]) == (1, 0, 0)
    assert kernel_steps([0], block_size=16, n_pages=256,
                        widths=[64]) == (64, 0, 0)
    assert kernel_steps([300], block_size=16, n_pages=256, widths=[128],
                        window=40) == (2, 2, 3)


def _reference_cases():
    """Seeds 0 and 1 in one group of pages, bf16 and fp8 caches; the
    "groups", "loop" and "heads" geometries with bf16 caches. (With an fp8
    cache, the "groups" case at seed 2 has one output element outside the
    tolerance: a bf16 score rounds the other way after the gather oracle's
    dot sums in another order, as it does for the one-page-per-step kernel
    of earlier versions; fp8 over several groups is checked with f32
    scores in test_kernel_mla_shape_and_scales.)"""
    names = {jnp.bfloat16: "bfloat16", jnp.float8_e4m3fn: "float8_e4m3fn"}
    for seed, tag, geometry, dtypes in (
            (0, "0", "pages", names), (1, "1", "pages", names),
            (2, "groups", "groups", [jnp.bfloat16]),
            (2, "loop", "loop", [jnp.bfloat16]),
            (3, "heads", "heads", [jnp.bfloat16])):
        for window in (None, 7):
            for dtype in dtypes:
                yield pytest.param(dtype, window, seed, geometry,
                                   id=f"{tag}-{window}-{names[dtype]}")


@pytest.mark.parametrize("dtype,window,seed,geometry", _reference_cases())
def test_kernel_matches_gather_reference(dtype, window, seed, geometry):
    g = GEOMETRY[geometry]
    B, Hkv, G, Dk = g["B"], g["Hkv"], g["G"], g["Dk"]
    n_pages, bs = g["n_pages"], g["bs"]
    n_blocks, bt, lengths, poison, rng = _random_paged_case(
        seed, B=B, n_pages=n_pages, bs=bs, dtype=dtype,
        lengths=g["lengths"])
    k = _fill(rng, (n_blocks, Hkv, bs, Dk), dtype, poison)
    v = _fill(rng, (n_blocks, Hkv, bs, Dk), dtype, poison)
    q = jnp.asarray(rng.normal(size=(B, Hkv, G, Dk)), jnp.bfloat16)
    kw = dict(window=window, scale=math.sqrt(Dk), scale_mode="div",
              score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
              out_dtype=jnp.bfloat16)
    got = paged_decode_attention(q, k, v, bt, lengths, interpret=True, **kw)
    want = ref.paged_decode_attention_ref(q, k, v, bt, lengths, **kw)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), "stale/dead entries leaked into output"
    # f32-summation-order tolerance only: a masking leak shows up as NaN or
    # a wildly wrong row, not a sub-percent wiggle (bitwise parity against
    # the in-repo gather path is asserted in test_layer_fused_matches_gather)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-5)


def test_kernel_ignores_nan_in_unreferenced_blocks():
    """The kernel must never *fetch* a block that only stale/dead table
    entries point at: with NaN in those blocks, its output is finite and
    bit-identical to the same case with them zeroed. (The gather reference
    cannot pass this — it materializes every table slot and 0 * NaN = NaN —
    which is exactly the hazard the in-kernel clamp removes.) Both
    geometries: in "groups" the dead pages of a partial group sit beside
    live ones in the step that copies them, and a window skips groups."""
    dtype = jnp.bfloat16
    for geometry, window in (("pages", None), ("groups", None),
                             ("groups", 7), ("loop", None), ("loop", 7)):
        g = GEOMETRY[geometry]
        B, Hkv, G, Dk = g["B"], g["Hkv"], g["G"], g["Dk"]
        n_pages, bs = g["n_pages"], g["bs"]
        n_blocks, bt, lengths, poison, rng = _random_paged_case(
            2, B=B, n_pages=n_pages, bs=bs, dtype=dtype,
            lengths=g["lengths"])
        kw = dict(window=window, scale=math.sqrt(Dk), scale_mode="div",
                  score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
                  out_dtype=jnp.bfloat16)

        def run(poison_value):
            r = np.random.default_rng(99)
            k = _fill(r, (n_blocks, Hkv, bs, Dk), dtype, poison, poison_value)
            v = _fill(r, (n_blocks, Hkv, bs, Dk), dtype, poison, poison_value)
            q = jnp.asarray(r.normal(size=(B, Hkv, G, Dk)), jnp.bfloat16)
            return np.asarray(paged_decode_attention(
                q, k, v, bt, lengths, interpret=True, **kw), np.float32)

        with_nan = run(np.nan)
        assert np.all(np.isfinite(with_nan)), \
            f"kernel fetched a stale/dead block ({geometry}, {window})"
        np.testing.assert_array_equal(with_nan, run(0.0))


def test_kernel_never_reads_past_length_exact_boundary():
    """Length exactly at a page boundary, mid-page, and 1: the first masked
    position sits in a NaN-free block's garbage tail as well as in poisoned
    stale blocks — output must equal a reference computed from a cache whose
    out-of-length entries were overwritten with a *different* value."""
    B, Hkv, G, Dk, n_pages, bs = 3, 1, 2, 16, 4, 4
    rng = np.random.default_rng(3)
    n_blocks = 1 + B * n_pages
    lengths = jnp.asarray([8, 5, 1], jnp.int32)    # boundary, mid-page, min
    bt = np.full((B, n_pages), -1, np.int32)
    ids = iter(range(1, n_blocks))
    for b in range(B):
        for pg in range(-(-int(lengths[b]) // bs)):
            bt[b, pg] = next(ids)
    bt = jnp.asarray(bt)
    k = rng.normal(size=(n_blocks, Hkv, bs, Dk)).astype(np.float32)
    v = rng.normal(size=(n_blocks, Hkv, bs, Dk)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, Hkv, G, Dk)), jnp.bfloat16)
    kw = dict(scale=math.sqrt(Dk), scale_mode="div",
              score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
              out_dtype=jnp.bfloat16)

    def run(karr, varr):
        return np.asarray(paged_decode_attention(
            q, jnp.asarray(karr, jnp.bfloat16), jnp.asarray(varr,
            jnp.bfloat16), bt, lengths, interpret=True, **kw), np.float32)

    base = run(k, v)
    k2, v2 = k.copy(), v.copy()
    for b in range(B):                       # scribble every dead position
        for pos in range(int(lengths[b]), n_pages * bs):
            pg, off = divmod(pos, bs)
            blk = int(bt[b, pg])
            if blk >= 0:
                k2[blk, :, off] = 1e4
                v2[blk, :, off] = -1e4
    np.testing.assert_array_equal(base, run(k2, v2))


@pytest.mark.parametrize("window", [None, 5])
def test_kernel_head_major_pages_match_dense_attention(window):
    """The page layout itself, checked against attention over the logical
    (B, S, Hkv, D) K/V — an oracle that shares no layout code with the
    kernel or ``paged_decode_attention_ref``. Pages are scattered
    head-major by hand, every head and token distinct; the last live page
    carries a stale tail past the row's length, dead table entries point at
    stale poisoned blocks or are -1 (clamped to a poisoned trash block 0),
    and the window drops early keys. Blocks of 8 tokens, the smallest the
    TPU compiler tiles."""
    B, Hkv, G, D, n_pages, bs = 3, 2, 2, 16, 4, 8
    rng = np.random.default_rng(11)
    S = n_pages * bs
    lengths = np.asarray([S, 13, 6], np.int32)
    bf = lambda x: np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    k_log = bf(rng.normal(size=(B, S, Hkv, D)))
    v_log = bf(rng.normal(size=(B, S, Hkv, D)))
    q = bf(rng.normal(size=(B, Hkv, G, D)))
    n_live = B * n_pages
    n_blocks = 1 + n_live + 2                 # trash, live, 2 stale
    k_pages = np.full((n_blocks, Hkv, bs, D), POISON, np.float32)
    v_pages = np.full((n_blocks, Hkv, bs, D), -POISON, np.float32)
    tables = np.full((B, n_pages), -1, np.int32)
    ids = iter(rng.permutation(np.arange(1, 1 + n_live)))
    for b in range(B):
        for pg in range(-(-int(lengths[b]) // bs)):
            blk = tables[b, pg] = next(ids)
            for i in range(bs):
                if pg * bs + i < lengths[b]:      # the rest stays stale
                    k_pages[blk, :, i] = k_log[b, pg * bs + i]
                    v_pages[blk, :, i] = v_log[b, pg * bs + i]
    tables[1, -1] = n_blocks - 1              # stale id past row 1's pages
    kw = dict(window=window, scale=math.sqrt(D), scale_mode="div",
              out_dtype=jnp.float32)
    args = (jnp.asarray(q), jnp.asarray(k_pages, jnp.bfloat16),
            jnp.asarray(v_pages, jnp.bfloat16), jnp.asarray(tables),
            jnp.asarray(lengths))
    got = np.asarray(paged_decode_attention(*args, interpret=True, **kw))

    want = np.zeros((B, Hkv, G, D), np.float32)
    for b in range(B):
        ln = int(lengths[b])
        lo = 0 if window is None else max(0, ln - window)
        for h in range(Hkv):
            s = q[b, h] @ k_log[b, lo:ln, h].T / math.sqrt(D)   # (G, keys)
            p = np.exp(s - s.max(-1, keepdims=True))
            want[b, h] = (p / p.sum(-1, keepdims=True)) @ v_log[b, lo:ln, h]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, np.asarray(ref.paged_decode_attention_ref(*args, **kw)),
        rtol=1e-5, atol=1e-5)


def test_kernel_mla_shape_and_scales():
    """MLA-absorbed shape: Hkv=1, H query heads, rope second operand,
    v = k (latent), multiplied scale, f32 all the way. Plus the fp8
    per-tensor dequant scales path (k_scale/v_scale != 1). In one group
    of pages, and over partial groups with 24-lane latents (through the
    grid) and 128-lane ones (copied in the kernel's loop)."""
    B, H = 2, 4
    for n_pages, bs, fixed, r, dr in ((4, 4, (), 24, 8),
                                      (20, 8, (129,), 24, 8),
                                      (20, 8, (129,), 128, 128)):
        n_blocks, bt, lengths, poison, rng = _random_paged_case(
            7, B=B, n_pages=n_pages, bs=bs, dtype=jnp.bfloat16,
            lengths=fixed)
        ckv = _fill(rng, (n_blocks, 1, bs, r), jnp.bfloat16, poison)
        kr = _fill(rng, (n_blocks, 1, bs, dr), jnp.bfloat16, poison)
        q1 = jnp.asarray(rng.normal(size=(B, 1, H, r)), jnp.float32)
        q2 = jnp.asarray(rng.normal(size=(B, 1, H, dr)), jnp.float32)
        kw = dict(q2=q2, k2=kr, scale=1.0 / math.sqrt(r + dr),
                  scale_mode="mul", out_dtype=jnp.float32)
        got = paged_decode_attention(q1, ckv, None, bt, lengths,
                                     interpret=True, **kw)
        want = ref.paged_decode_attention_ref(q1, ckv, None, bt, lengths,
                                              **kw)
        got = np.asarray(got, np.float32)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   rtol=1e-4, atol=1e-6)

        # fp8 cache with real per-tensor dequant scales
        kq = _fill(rng, (max(16, 1 + B * n_pages), 1, bs, r),
                   jnp.float8_e4m3fn, [])
        btq = jnp.asarray(np.arange(1, 1 + B * n_pages).reshape(B, n_pages))
        ln = jnp.asarray([n_pages * bs, 3], jnp.int32)
        kw = dict(scale=math.sqrt(r), scale_mode="div", k_scale=0.25,
                  v_scale=2.0, out_dtype=jnp.float32)
        got = paged_decode_attention(q1, kq, None, btq, ln, interpret=True,
                                     **kw)
        want = ref.paged_decode_attention_ref(q1, kq, None, btq, ln, **kw)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-4, atol=1e-6)


if HAS_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000), st.integers(1, 4), st.integers(2, 6),
           st.sampled_from([2, 4, 8]), st.one_of(st.none(),
                                                 st.integers(1, 24)))
    def test_kernel_matches_reference_property(seed, B, n_pages, bs, window):
        Hkv, G, Dk = 2, 2, 16
        n_blocks, bt, lengths, poison, rng = _random_paged_case(
            seed, B=B, n_pages=n_pages, bs=bs, dtype=jnp.bfloat16)
        k = _fill(rng, (n_blocks, Hkv, bs, Dk), jnp.bfloat16, poison)
        v = _fill(rng, (n_blocks, Hkv, bs, Dk), jnp.bfloat16, poison)
        q = jnp.asarray(rng.normal(size=(B, Hkv, G, Dk)), jnp.bfloat16)
        kw = dict(window=window, scale=math.sqrt(Dk), scale_mode="div",
                  score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
                  out_dtype=jnp.bfloat16)
        got = np.asarray(paged_decode_attention(q, k, v, bt, lengths,
                                                interpret=True, **kw),
                         np.float32)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(
            got, np.asarray(ref.paged_decode_attention_ref(
                q, k, v, bt, lengths, **kw), np.float32),
            rtol=1e-2, atol=1e-5)


# ---------------------------------------------------------------------------
# layer-level dispatch: the kernel switch lives in use_fused_paged
# ---------------------------------------------------------------------------


def _layer_attention_case(paged_attn, ctx=None, window=None, kv_scales=None,
                          n_pages=4, positions=((9,), (4,)), d_head=16):
    cfg = L.AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, d_head=d_head,
                       window=window, kv_dequant_scales=kv_scales)
    rng = np.random.default_rng(11)
    specs = L.attn_specs("attn", cfg)
    key = jax.random.key(0)
    params = {}
    for path, ps in specs.items():
        key, sub = jax.random.split(key)
        node = params
        parts = path.split("/")[1:]
        for q in parts[:-1]:
            node = node.setdefault(q, {})
        node[parts[-1]] = (jax.random.normal(sub, ps.shape, jnp.float32)
                           * 0.05).astype(jnp.bfloat16)
    B, bs = 2, 4
    n_blocks = 1 + B * n_pages
    cache = {"k": jnp.asarray(rng.normal(size=(n_blocks, 2, bs, d_head)),
                              jnp.bfloat16),
             "v": jnp.asarray(rng.normal(size=(n_blocks, 2, bs, d_head)),
                              jnp.bfloat16)}
    bt = jnp.asarray(np.arange(1, 1 + B * n_pages).reshape(B, n_pages))
    x = jnp.asarray(rng.normal(size=(B, 1, 64)), jnp.bfloat16)
    positions = jnp.asarray(positions, jnp.int32)
    ctx = ctx or QuantContext()
    y, new_cache = L.attention(params, ctx, "attn", cfg, x, positions,
                               cache=cache, cache_pos=positions[:, 0],
                               block_tables=bt, paged_attn=paged_attn)
    return np.asarray(y, np.float32), new_cache


# "groups": 40 pages of 4 in groups of 32; one row one token past the first
# group, the other exactly one group long; a window of 6 skips group 0.
# "loop": the same with heads of 128, whose pages the kernel copies itself.
GROUPS = dict(n_pages=40, positions=((128,), (127,)))
GROUPS_6 = dict(n_pages=40, positions=((140,), (127,)))


@pytest.mark.parametrize("window,table", [
    pytest.param(None, {}, id="None"), pytest.param(6, {}, id="6"),
    pytest.param(None, GROUPS, id="groups-None"),
    pytest.param(6, GROUPS_6, id="groups-6"),
    pytest.param(None, dict(GROUPS, d_head=128), id="loop-None"),
    pytest.param(6, dict(GROUPS_6, d_head=128), id="loop-6")])
def test_layer_fused_matches_gather(window, table):
    yf, cf = _layer_attention_case("fused", window=window, **table)
    yg, cg = _layer_attention_case("gather", window=window, **table)
    np.testing.assert_array_equal(yf, yg)
    for name in ("k", "v"):                  # identical cache writes too
        np.testing.assert_array_equal(np.asarray(cf[name], np.float32),
                                      np.asarray(cg[name], np.float32))


def test_scan_mode_traced_window_fused_decode():
    """Scan-mode segments feed the kernel a *traced* per-layer window (a
    scanned-over int32 mixing the real window with the BIG_WINDOW sentinel
    for global layers): fused and gather decode must still agree bitwise."""
    from repro.models.registry import get_model
    model = get_model("qwen2p5_3b", smoke=True, n_layers=2, scan_layers=True,
                      sliding_window=6, global_attn_layers=(1,))
    params = model.init(jax.random.key(0))
    ctx = QuantContext()
    rng = np.random.default_rng(31)
    B, bs, nb = 2, 4, 16
    caches = model.init_paged_cache(B, nb, bs)
    bt = np.full((B, 4), -1, np.int32)
    ids = iter(range(1, nb))
    lens = [9, 5]
    for b in range(B):
        for pg in range(-(-lens[b] // bs)):
            bt[b, pg] = next(ids)
    toks = jnp.asarray(rng.integers(0, 200, (B, 12)), jnp.int32)
    _, caches = model.prefill_chunk(
        params, toks, caches, ctx, start_pos=jnp.zeros((B,), jnp.int32),
        valid_len=jnp.asarray(lens, jnp.int32), block_tables=jnp.asarray(bt))
    for b in range(B):
        pg = lens[b] // bs
        if bt[b, pg] < 0:
            bt[b, pg] = next(ids)
    tok = jnp.asarray(rng.integers(0, 200, (B, 1)), jnp.int32)
    outs = {}
    for pa in ("fused", "gather"):
        lg, _ = model.decode_step(params, tok, jnp.asarray(lens, jnp.int32),
                                  caches, ctx, block_tables=jnp.asarray(bt),
                                  paged_attn=pa)
        outs[pa] = np.asarray(lg, np.float32)
    np.testing.assert_array_equal(outs["fused"], outs["gather"])


def test_fused_dispatch_predicate():
    """The single switch: MP formats on the attention BGEMMs, probe mode,
    and registry traces all force the gather path."""
    ctx = QuantContext()
    assert L.use_fused_paged(ctx, "layers/0/attn", "fused")
    assert not L.use_fused_paged(ctx, "layers/0/attn", "gather")
    mp_ctx = QuantContext(mode="mp",
                          mp={"layers/0/attn/qk_matmul": "fp8_e4m3"})
    assert not L.use_fused_paged(mp_ctx, "layers/0/attn", "fused")
    assert L.use_fused_paged(mp_ctx, "layers/1/attn", "fused")
    mp_ctx2 = QuantContext(mode="mp",
                           mp={"layers/0/attn/av_matmul": "fp8_e5m2"})
    assert not L.use_fused_paged(mp_ctx2, "layers/0/attn", "fused")
    assert not L.use_fused_paged(QuantContext(mode="probe"), "x", "fused")
    assert not L.use_fused_paged(QuantContext(registry=[]), "x", "fused")
    with pytest.raises(AssertionError):
        L.use_fused_paged(ctx, "x", "flash")


def test_layer_mp_on_bgemm_falls_back_to_gather():
    """A layer whose qk_matmul carries an MP format must produce the exact
    quantized reference output even when paged_attn='fused' is requested."""
    mp = {"attn/qk_matmul": "fp8_e4m3"}
    ctx_mp = QuantContext(mode="mp", mp=mp, act_scale_token=True)
    yf, _ = _layer_attention_case("fused", ctx=ctx_mp)
    yg, _ = _layer_attention_case("gather", ctx=ctx_mp)
    np.testing.assert_array_equal(yf, yg)


# ---------------------------------------------------------------------------
# KV dequant scales: one mapping, both read paths
# ---------------------------------------------------------------------------


def test_layer_scaled_kv_fused_matches_gather():
    """Non-unit ``kv_dequant_scales`` must dequantize identically on both
    paged read paths (regression: the gather fallback used to drop them,
    silently diverging from the fused kernel's in-register dequant)."""
    scales = (("k", 0.5), ("v", 2.0))
    yf, _ = _layer_attention_case("fused", kv_scales=scales)
    yg, _ = _layer_attention_case("gather", kv_scales=scales)
    np.testing.assert_array_equal(yf, yg)
    # and the scales actually bite — a unit-scale run differs
    yu, _ = _layer_attention_case("gather")
    assert not np.array_equal(yg, yu)


def test_paged_gather_applies_dequant_scales():
    """layers.paged_gather with scales == the kernel oracle's gathered
    dequant (f32 multiply then cast), exercised through fp8 storage where
    the rounding point actually matters; absent/unit entries stay a plain
    upcast bit-identical to the legacy gather."""
    rng = np.random.default_rng(5)
    cache = {"k": jnp.asarray(rng.normal(size=(7, 2, 4, 8)),
                              jnp.float8_e4m3fn),
             "v": jnp.asarray(rng.normal(size=(7, 2, 4, 8)),
                              jnp.float8_e4m3fn)}
    bt = jnp.asarray([[1, 3, -1], [2, 6, 4]], jnp.int32)
    g, _ = L.paged_gather(cache, bt, jnp.bfloat16,
                          {"k": 0.5, "v": 2.0})
    for name, s in (("k", 0.5), ("v", 2.0)):
        want = ref._paged_deq(cache[name], bt, jnp.bfloat16, s)
        np.testing.assert_array_equal(np.asarray(g[name], np.float32),
                                      np.asarray(want, np.float32))
    g1, _ = L.paged_gather(cache, bt, jnp.bfloat16, {"k": 1.0})
    legacy, _ = L.paged_gather(cache, bt, jnp.bfloat16)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(g1[name], np.float32),
                                      np.asarray(legacy[name], np.float32))


def test_mla_fused_rejects_nonunit_scales():
    """The fused absorbed-MLA path cannot reproduce the gather path's bf16
    rounding of scaled latents, so it must refuse non-unit scales instead
    of silently diverging."""
    cfg = L.MLAConfig(d_model=32, n_heads=2, q_lora_rank=8, kv_lora_rank=8,
                      qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                      absorb_decode=True)
    rng = np.random.default_rng(7)
    B, bs, n_pages = 1, 4, 2
    p = {"kv_b_proj": {"w": jnp.asarray(
        rng.normal(size=(2 * (8 + 8), 8)) * 0.05, jnp.bfloat16)}}
    qn = jnp.asarray(rng.normal(size=(B, 1, 2, 8)), jnp.bfloat16)
    qr = jnp.asarray(rng.normal(size=(B, 1, 2, 4)), jnp.bfloat16)
    cache = {"ckv": jnp.asarray(rng.normal(size=(5, 1, bs, 8)), jnp.bfloat16),
             "kr": jnp.asarray(rng.normal(size=(5, 1, bs, 4)), jnp.bfloat16)}
    bt = jnp.asarray([[1, 2]], jnp.int32)
    pos = jnp.asarray([[5]], jnp.int32)
    with pytest.raises(ValueError, match="non-unit"):
        L._mla_decode_absorbed_paged(p, QuantContext(), "mla", cfg, qn, qr,
                                     cache, bt, pos,
                                     scales={"ckv": 0.5, "kr": 0.5})
