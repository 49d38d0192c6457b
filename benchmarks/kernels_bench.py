"""Kernel microbenchmarks.

Interpret-mode executes kernel bodies in Python (correctness only), so the
timing rows measure the XLA lowering of the *same computation* (the
deployment fallback path) plus the interpret-mode allclose check per shape.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, write_rows_json
from repro.kernels import ops, ref
from repro.kernels.quant_cast import quantize_fp8


def _time(fn, *args, iters=5):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="also write the rows as JSON (default path "
                         "BENCH_kernels.json when the flag is given bare)")
    args = ap.parse_args()
    key = jax.random.key(0)
    print("kernel,shape,us_xla_path,interpret_ok")
    for (M, K, N) in ((256, 512, 256), (512, 1024, 512)):
        x = jax.random.normal(key, (M, K), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (N, K), jnp.float32)
        xq, sx = quantize_fp8(x, interpret=True)
        wq, sw = quantize_fp8(w, interpret=True)
        want = ref.fp8_matmul_ref(xq, wq, sx, sw)
        from repro.kernels import fp8_matmul
        got = fp8_matmul(xq, wq, sx, sw, block_m=128, block_n=128,
                         block_k=256, interpret=True)
        ok = bool(np.allclose(np.asarray(got, np.float32),
                              np.asarray(want, np.float32), rtol=2e-2,
                              atol=2e-2))
        fn = jax.jit(lambda a, b, s1, s2: ref.fp8_matmul_ref(a, b, s1, s2))
        us = _time(fn, xq, wq, sx, sw)
        print(f"fp8_matmul,{M}x{K}x{N},{us:.1f},{ok}")
        emit(f"kernels.fp8_matmul_{M}x{K}x{N}", us, f"allclose={ok}")

    B, H, T, D = 2, 4, 512, 64
    q = jax.random.normal(key, (B, H, T, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 2), (B, H, T, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 3), (B, H, T, D), jnp.float32)
    from repro.kernels import mp_flash_attention
    got = mp_flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                             interpret=True)
    want = ref.mp_flash_attention_ref(q, k, v, causal=True)
    ok = bool(np.allclose(np.asarray(got, np.float32),
                          np.asarray(want, np.float32), rtol=5e-2, atol=5e-3))
    fn = jax.jit(lambda a, b, c: ref.mp_flash_attention_ref(a, b, c))
    us = _time(fn, q, k, v)
    emit(f"kernels.mp_flash_attention_{B}x{H}x{T}x{D}", us, f"allclose={ok}")

    paged_attention_rows(key)

    if args.json:
        write_rows_json(args.json)


def paged_attention_rows(key) -> None:
    """Fused paged-decode kernel vs the gather XLA path across block counts
    and live-token fractions. The kernel itself runs interpret-mode
    (correctness); the timing rows compare the XLA gather computation at
    full provisioned capacity vs restricted to each row's live pages — the
    read set the fused kernel touches — so the capacity-vs-live traffic gap
    the kernel closes is visible in XLA wall time too."""
    import math

    from repro.kernels.paged_attention import paged_decode_attention

    B, Hkv, G, D, bs = 4, 2, 2, 64, 16
    for n_pages, live_frac in ((8, 0.25), (8, 0.5), (16, 0.25), (16, 0.125)):
        n_blocks = 1 + B * n_pages
        k1, k2, k3 = (jax.random.fold_in(key, 10 + i) for i in range(3))
        kc = jax.random.normal(k1, (n_blocks, Hkv, bs, D),
                               jnp.float32).astype(jnp.bfloat16)
        vc = jax.random.normal(k2, (n_blocks, Hkv, bs, D),
                               jnp.float32).astype(jnp.bfloat16)
        q = jax.random.normal(k3, (B, Hkv, G, D),
                              jnp.float32).astype(jnp.bfloat16)
        live = max(int(live_frac * n_pages * bs), 1)
        live_pages = -(-live // bs)
        lengths = jnp.full((B,), live, jnp.int32)
        tables = np.full((B, n_pages), -1, np.int32)
        ids = iter(range(1, n_blocks))
        for b in range(B):
            for pg in range(live_pages):
                tables[b, pg] = next(ids)
        bt = jnp.asarray(tables)
        kw = dict(scale=math.sqrt(D), scale_mode="div",
                  score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
                  out_dtype=jnp.bfloat16)
        got = paged_decode_attention(q, kc, vc, bt, lengths, interpret=True,
                                     **kw)
        want = ref.paged_decode_attention_ref(q, kc, vc, bt, lengths, **kw)
        ok = bool(np.allclose(np.asarray(got, np.float32),
                              np.asarray(want, np.float32),
                              rtol=1e-2, atol=1e-5))
        fn = jax.jit(lambda qq, kk, vv, tt, ln: ref.paged_decode_attention_ref(
            qq, kk, vv, tt, ln, **kw))
        us_capacity = _time(fn, q, kc, vc, bt, lengths)
        us_live = _time(fn, q, kc, vc, bt[:, :live_pages], lengths)
        blk_kb = 2 * bs * Hkv * D * kc.dtype.itemsize / 1024
        emit(f"kernels.paged_attention_p{n_pages}_live{live_frac}",
             us_capacity,
             f"gather XLA at capacity ({n_pages} pages/row, "
             f"{n_pages * blk_kb:.0f} KB KV read/row); live-only "
             f"{us_live:.1f}us ({live_pages} pages, "
             f"{live_pages * blk_kb:.0f} KB — the fused kernel's read set); "
             f"fused interpret allclose={ok}")


if __name__ == "__main__":
    main()
