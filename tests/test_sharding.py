"""Sharding rules: divisibility fallback, ZeRO, roofline HLO parsing."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.roofline import parse_collective_bytes
from repro.distributed import sharding as shd
from repro.nn.spec import ParamSpec


def _mesh():
    # single-device "mesh" with the production axis names: rule logic only
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def test_partition_spec_divisible():
    mesh = jax.make_mesh((1, 2), ("data", "model"), devices=jax.devices() * 2) \
        if len(jax.devices()) >= 2 else None
    # use abstract reasoning through a fake mesh via axis sizes on 1 device
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    ps = shd.partition_spec(ParamSpec((64, 128), ("ffn", "embed")), mesh)
    assert ps == P("model")  # 64 % 1 == 0 -> sharded (trivially)


def test_divisibility_fallback_replicates():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    ps = shd.partition_spec(ParamSpec((25, 64), ("heads", None)), FakeMesh())
    assert ps == P()  # 25 % 16 != 0 -> replicated
    ps2 = shd.partition_spec(ParamSpec((32, 64), ("heads", None)), FakeMesh())
    assert ps2 == P("model")


def test_kv_head_dim_fallback():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = ParamSpec((128, 32768, 8, 128),
                     ("act_batch", None, "kv_heads", "head_dim"))
    ps = shd.partition_spec(spec, FakeMesh())
    # kv_heads=8 not divisible -> head_dim picks up 'model'
    assert ps == P("data", None, None, "model")


def test_zero_sharding_adds_data_axis():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = ParamSpec((1024, 4096), ("ffn", "embed"))
    base = shd.partition_spec(spec, FakeMesh())
    zero = shd.zero_partition_spec(spec, FakeMesh())
    assert base == P("model")
    assert zero == P("model", "data")


def test_fsdp_rules_shard_embed():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = ParamSpec((1024, 4096), ("ffn", "embed"))
    ps = shd.partition_spec(spec, FakeMesh(), shd.FSDP_RULES)
    assert ps == P("model", "data")


def test_shard_hint_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shd.shard_hint(x, "data", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_parse_collective_bytes():
    hlo = """
  %ag = bf16[64,1024]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[128]{0} all-reduce(%y), to_apply=%sum
  %rs = (f32[32]{0}, f32[32]{0}) reduce-scatter(%a, %b), dimensions={0}
  %a2a = bf16[8,16]{1,0} all-to-all(%z), dimensions={0}
  %cp = u8[4]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %notacoll = f32[999]{0} add(%p, %q)
"""
    got = parse_collective_bytes(hlo)
    assert got["all-gather"] == 64 * 1024 * 2
    assert got["all-reduce"] == 128 * 4
    assert got["reduce-scatter"] == 2 * 32 * 4
    assert got["all-to-all"] == 8 * 16 * 2
    assert got["collective-permute"] == 4
    assert got["total"] == sum(got[k] for k in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute"))


def test_grad_compression_roundtrip(rng):
    """fp8 gradient compression w/ error feedback: bounded per-step error,
    vanishing accumulated bias (the distributed-optimization trick)."""
    from repro.distributed.grad_compress import compress_decompress
    g = jax.random.normal(rng, (256, 128), jnp.float32)
    err = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    acc_ref = jnp.zeros_like(g)
    for i in range(8):
        gi = g * (1.0 + 0.1 * i)
        out, err = compress_decompress(gi, err)
        acc = acc + out
        acc_ref = acc_ref + gi
    rel = float(jnp.linalg.norm(acc - acc_ref) / jnp.linalg.norm(acc_ref))
    assert rel < 0.02  # error feedback keeps the accumulated bias tiny


# ---- serving-mesh page shardings (PR 7) ----

class _Mesh2x2:
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 2}


def test_kv_page_spec_shards_blocks_and_heads():
    from repro.nn.layers import AttnConfig, kv_page_spec
    cfg = AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, d_head=16)
    specs = kv_page_spec(cfg, n_blocks=8, block_size=4)
    for name in ("k", "v"):
        ps = shd.partition_spec(specs[name], _Mesh2x2())
        # pages over data, kv heads over model; token dim replicated
        assert ps == P("data", "model"), (name, ps)


def test_kv_page_spec_gqa_head_fallback():
    """kv_heads % model != 0 -> heads replicate and head_dim picks up
    'model' (the divisibility fallback the engine's gather path relies on)."""
    from repro.nn.layers import AttnConfig, kv_page_spec
    cfg = AttnConfig(d_model=48, n_heads=3, n_kv_heads=3, d_head=16)
    ps = shd.partition_spec(kv_page_spec(cfg, 8, 4)["k"], _Mesh2x2())
    assert ps == P("data", None, None, "model")

    class Mesh4:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}
    # neither kv_heads (3) nor head_dim (10) divides model=4 -> fully
    # replicated on model; pages still shard over data. No silent
    # wrong-shard: the spec must fall back, never mis-split.
    cfg2 = AttnConfig(d_model=30, n_heads=3, n_kv_heads=3, d_head=10)
    ps2 = shd.partition_spec(kv_page_spec(cfg2, 8, 4)["k"], Mesh4())
    assert ps2 == P("data")


def test_kv_page_spec_block_count_fallback():
    """n_blocks % data != 0 -> the pool dim replicates (matches
    PagedCachePool.plan_blocks turning shard_pages off)."""
    from repro.nn.layers import AttnConfig, kv_page_spec
    cfg = AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, d_head=16)
    ps = shd.partition_spec(kv_page_spec(cfg, n_blocks=7, block_size=4)["k"],
                            _Mesh2x2())
    assert ps == P(None, "model")


def test_mla_page_spec_mesh_shardings():
    from repro.nn.layers import MLAConfig, mla_page_spec
    cfg = MLAConfig(d_model=32, n_heads=2, q_lora_rank=8, kv_lora_rank=8,
                    qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8)
    specs = mla_page_spec(cfg, n_blocks=8, block_size=4)
    ckv = shd.partition_spec(specs["ckv"], _Mesh2x2())
    kr = shd.partition_spec(specs["kr"], _Mesh2x2())
    assert ckv == P("data", None, None, "model")  # latent rank over model
    assert kr == P("data")                   # rope dim replicated

    class Mesh3:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 3}
    # kv_lora_rank=8 % 3 != 0 -> latent replicates instead of mis-splitting
    assert shd.partition_spec(specs["ckv"], Mesh3()) == P("data")


def test_plan_blocks_geometry():
    from repro.serve.cache_pool import PagedCachePool
    # single shard: worst case 1 + n_slots * ceil(max_len/bs), never sharded
    n, shard, bps = PagedCachePool.plan_blocks(4, 64, 16)
    assert (n, shard, bps) == (1 + 4 * 4, False, 17)
    # data=2, everything divides: per-shard trash block, even split
    n, shard, bps = PagedCachePool.plan_blocks(4, 64, 16, data_shards=2)
    assert shard and n == 2 * (1 + 2 * 4) and bps == n // 2
    # explicit n_blocks that doesn't divide -> replicated pool
    n, shard, bps = PagedCachePool.plan_blocks(4, 64, 16, n_blocks=9,
                                               data_shards=2)
    assert (n, shard, bps) == (9, False, 9)
    # slots don't divide -> replicated even if blocks would
    n, shard, bps = PagedCachePool.plan_blocks(3, 64, 16, data_shards=2)
    assert not shard and bps == n


def test_size_n_blocks_profile_sizing():
    from repro.serve.cache_pool import PagedCachePool
    profile = [(16, 8)] * 8
    worst, _, _ = PagedCachePool.plan_blocks(4, 24, 8)
    n = PagedCachePool.size_n_blocks(profile, 4, 8)
    assert 1 + 3 <= n <= worst  # >= largest request + trash, <= worst case
    # short requests against a long max_len: auto sizing beats worst case
    worst_long, _, _ = PagedCachePool.plan_blocks(4, 256, 8)
    n_long = PagedCachePool.size_n_blocks(profile, 4, 8)
    assert n_long < worst_long
    # sharded sizing returns a multiple of data_shards
    n2 = PagedCachePool.size_n_blocks(profile, 4, 8, data_shards=2)
    assert n2 % 2 == 0
    import pytest
    with pytest.raises(ValueError):
        PagedCachePool.size_n_blocks([], 4, 8)
