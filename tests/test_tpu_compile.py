"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on a CPU host and catch what
the Pallas interpreter cannot: block shapes that do not tile, unaligned
dynamic slices, scratch that overflows VMEM. Nothing runs; a pass says the
chip's compiler accepts the kernel at these shapes, not that it is fast or
right (the interpreter tests in ``test_paged_attention.py`` check values).

The topology is described inside a module-scoped fixture: only the worker
that runs this file loads the TPU library.
"""
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import deepseek_v3_671b, llama3_1b
from repro.kernels import (amax, fp8_matmul, mp_flash_attention,
                           paged_decode_attention, quantize_fp8)

LLAMA = llama3_1b.config()
MLA = deepseek_v3_671b.config()
MAX_LEN = 1024          # the largest max_len chip_smoke.py serves
BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _paged_args(sharding, *, kv_dtype, block, heads, width, q_heads,
                q_dtype=jnp.bfloat16, max_len=MAX_LEN):
    n_pages = max_len // block
    n_blocks = 1 + BATCH * n_pages
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return dict(
        q=s((BATCH, heads, q_heads, width), q_dtype),
        pages=s((n_blocks, heads, block, width), kv_dtype),
        bt=s((BATCH, n_pages), jnp.int32),
        lengths=s((BATCH,), jnp.int32))


@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8"])
def test_paged_decode_compiles_llama3_1b(one_chip, kv_dtype, block):
    hkv = LLAMA.n_kv_heads
    a = _paged_args(one_chip, kv_dtype=kv_dtype, block=block, heads=hkv,
                    width=LLAMA.d_head, q_heads=LLAMA.n_heads // hkv)

    def step(q, k, v, bt, lengths):
        return paged_decode_attention(
            q, k, v, bt, lengths, scale=math.sqrt(LLAMA.d_head),
            score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
            out_dtype=jnp.bfloat16, interpret=False)

    _compile(step, a["q"], a["pages"], a["pages"], a["bt"], a["lengths"])


def test_paged_decode_compiles_llama3_1b_longest(one_chip):
    """The longest ``max_len`` the kernel with one page per grid step
    compiled at under the default scoped VMEM (32720 at these shapes;
    32736 was refused): grouping pages must not lower it."""
    hkv = LLAMA.n_kv_heads
    a = _paged_args(one_chip, kv_dtype=jnp.bfloat16, block=16, heads=hkv,
                    width=LLAMA.d_head, q_heads=LLAMA.n_heads // hkv,
                    max_len=32720)

    def step(q, k, v, bt, lengths):
        return paged_decode_attention(
            q, k, v, bt, lengths, scale=math.sqrt(LLAMA.d_head),
            score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
            out_dtype=jnp.bfloat16, interpret=False)

    _compile(step, a["q"], a["pages"], a["pages"], a["bt"], a["lengths"])


def test_paged_decode_compiles_at_benchmark_shapes(one_chip):
    """The benchmark's chat cell (Qwen2.5-3B): 32 rows, 2 KV heads of 128
    with 8 query heads each, a table of 256 pages of 16, bf16."""
    B, hkv, G, D, block, n_pages = 32, 2, 8, 128, 16, 256
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pages = s((1 + B * n_pages, hkv, block, D), jnp.bfloat16)

    def step(q, k, v, bt, lengths):
        return paged_decode_attention(
            q, k, v, bt, lengths, scale=math.sqrt(D),
            score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
            out_dtype=jnp.bfloat16, interpret=False)

    _compile(step, s((B, hkv, G, D), jnp.bfloat16), pages, pages,
             s((B, n_pages), jnp.int32), s((B,), jnp.int32))


def test_paged_decode_compiles_mla_absorbed(one_chip):
    """MLA absorbed decode: one latent "head", H query heads in f32, the
    RoPE second operand, values read from the key pages (``v=None``)."""
    m = MLA.mla_cfg
    a = _paged_args(one_chip, kv_dtype=jnp.bfloat16, block=16, heads=1,
                    width=m.kv_lora_rank, q_heads=m.n_heads,
                    q_dtype=jnp.float32)
    r = _paged_args(one_chip, kv_dtype=jnp.bfloat16, block=16, heads=1,
                    width=m.qk_rope_dim, q_heads=m.n_heads,
                    q_dtype=jnp.float32)

    def step(q, ckv, q2, kr, bt, lengths):
        return paged_decode_attention(
            q, ckv, None, bt, lengths, q2=q2, k2=kr,
            scale=1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim),
            scale_mode="mul", out_dtype=jnp.float32, interpret=False)

    _compile(step, a["q"], a["pages"], r["q"], r["pages"], a["bt"],
             a["lengths"])


@pytest.mark.parametrize("data,model", [(1, 4), (2, 2)])
def test_paged_decode_compiles_per_shard_under_mesh(topo, data, model):
    """The serving mesh path: the kernel per shard under ``shard_map``, with
    page-sharded pools translating global block ids to shard-local ones."""
    from repro.distributed.sharding import (ServingMeshLayout,
                                            serving_layout_scope)
    from repro.nn.layers import _paged_kernel_call
    from repro.serve.cache_pool import PagedCachePool
    mesh = Mesh(np.asarray(topo.devices).reshape(data, model),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    block = 16
    n_blocks, shard_pages, bps = PagedCachePool.plan_blocks(
        BATCH, MAX_LEN, block, data_shards=data)
    layout = ServingMeshLayout(mesh=mesh, data=data, model=model,
                               n_slots=BATCH, block_size=block,
                               n_blocks=n_blocks, shard_pages=shard_pages,
                               blocks_per_shard=bps)
    hkv, d = LLAMA.n_kv_heads, LLAMA.d_head
    s = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    page = s((n_blocks, hkv, block, d), jnp.bfloat16,
             P("data" if shard_pages else None, "model"))
    args = (s((BATCH, hkv, LLAMA.n_heads // hkv, d), jnp.bfloat16,
              P("data", "model")),
            page, page,
            s((BATCH, MAX_LEN // block), jnp.int32, P("data")),
            s((BATCH,), jnp.int32, P("data")))

    def step(q, k, v, bt, lengths):
        with serving_layout_scope(layout):
            return _paged_kernel_call(
                q, k, v, bt, lengths, scale=math.sqrt(d),
                score_dtype=jnp.bfloat16, probs_dtype=jnp.bfloat16,
                out_dtype=jnp.bfloat16, interpret=False)

    _compile(step, *args)


def test_mp_flash_attention_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1, LLAMA.n_heads, 512, LLAMA.d_head),
                             jnp.float8_e4m3fn, sharding=one_chip)
    _compile(lambda q, k, v: mp_flash_attention(q, k, v, 1.0, 1.0, 1.0,
                                                quant_probs=True),
             x, x, x)


def test_fp8_matmul_compiles(one_chip):
    x = jax.ShapeDtypeStruct((256, LLAMA.d_model), jnp.float8_e4m3fn,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((LLAMA.d_ff, LLAMA.d_model), jnp.float8_e4m3fn,
                             sharding=one_chip)
    _compile(lambda a, b: fp8_matmul(a, b, 1.0, 1.0), x, w)


@pytest.mark.parametrize("fn", [amax, quantize_fp8],
                         ids=["amax", "quantize_fp8"])
def test_quant_cast_compiles(one_chip, fn):
    x = jax.ShapeDtypeStruct((1024, LLAMA.d_model), jnp.bfloat16,
                             sharding=one_chip)
    _compile(fn, x)
