"""Sharding-rule logic on abstract meshes (no devices needed)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, ARCHS
from repro.distributed.sharding import (Parallelism, ShardingPolicy,
                                        attn_mode, padded_heads)

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _policy(arch, kind="train", mesh=MESH_1POD):
    cfg = get_config(arch)
    par = Parallelism.for_mesh(mesh)
    return ShardingPolicy(cfg, mesh, par, kind=kind), cfg


@pytest.mark.parametrize("arch,expect_train,expect_decode", [
    ("gemma2-27b", "heads", "heads"),
    ("zamba2-1.2b", "heads", "heads"),
    ("qwen2-72b", "expand", "head_dim"),
    ("yi-9b", "expand", "head_dim"),
    ("gemma3-12b", "expand", "head_dim"),
    ("internvl2-26b", "expand", "head_dim"),
    ("granite-moe-1b-a400m", "expand", "head_dim"),
    ("musicgen-medium", "expand", "head_dim"),
    ("llama4-maverick-400b-a17b", "expand", "head_dim"),
])
def test_attn_modes(arch, expect_train, expect_decode):
    cfg = get_config(arch)
    assert attn_mode(cfg, 16, "train") == expect_train
    assert attn_mode(cfg, 16, "decode") == expect_decode


def test_head_padding():
    assert padded_heads(get_config("llama4-maverick-400b-a17b"), 16,
                        "expand") == 48
    assert padded_heads(get_config("musicgen-medium"), 16, "expand") == 32
    assert padded_heads(get_config("qwen2-72b"), 16, "expand") == 64  # no pad


def test_param_specs_divisibility_fallback():
    policy, cfg = _policy("qwen2-72b")
    # wq with padded heads shards on model; wk (kv=8) stays replicated
    assert policy.spec((8192, 64, 128), ("embed", "q_heads", "head_dim")) \
        == P("data", "model")
    assert policy.spec((8192, 8, 128), ("embed", "kv_heads", "head_dim")) \
        == P("data")
    assert policy.fallbacks == []          # kv->None is a rule, not fallback
    # vocab padded divisible
    assert policy.spec((152064, 8192), ("vocab", "embed")) \
        == P("model", "data")
    # indivisible dim falls back to replication and is recorded
    spec = policy.spec((100, 8192), ("vocab", "embed"))
    assert spec == P(None, "data")
    assert policy.fallbacks


def test_multipod_fsdp_axes():
    policy, cfg = _policy("gemma2-27b", mesh=MESH_2POD)
    assert policy.parallel.batch_axes == ("pod", "data")
    assert policy.spec((4608, 32, 128), ("embed", "q_heads", "head_dim")) \
        == P(("pod", "data"), "model")


def test_long_context_shards_cache_seq():
    cfg = get_config("gemma2-27b")
    par = Parallelism.for_mesh(MESH_1POD)
    pol = ShardingPolicy(cfg, MESH_1POD, par, kind="decode",
                         shard_seq_kv=True)
    # batch=1 falls back; seq shards over data
    assert pol.spec((1, 524288, 16, 128),
                    ("batch", "seq_kv", "kv_heads", "head_dim")) \
        == P(None, "data", "model")


def test_decode_head_dim_mode_cache_sharding():
    policy, cfg = _policy("qwen2-72b", kind="decode")
    assert policy.spec((128, 32768, 8, 128),
                       ("batch", "seq_kv", "kv_heads", "head_dim")) \
        == P("data", None, None, "model")


@pytest.mark.parametrize("arch", ARCHS)
def test_no_unexpected_fallbacks_on_production_mesh(arch):
    """Every param of every arch must shard with zero fallbacks on 16x16."""
    from repro.models.model import build_model
    cfg = get_config(arch)
    par = Parallelism.for_mesh(MESH_1POD)
    pol = ShardingPolicy(cfg, MESH_1POD, par, kind="train")
    model = build_model(cfg, MESH_1POD, par, pol)
    cap = {}

    def only_p(key):
        p, ax = model.init(key)
        cap["ax"] = ax
        return p

    sds = jax.eval_shape(only_p, jax.random.PRNGKey(0))
    pol.tree_specs(sds, cap["ax"])
    assert pol.fallbacks == [], pol.fallbacks
