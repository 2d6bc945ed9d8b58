"""Sharding rules: fallback chains for the adversarial arch geometries."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding import (
    DECODE_RULES, DEFAULT_RULES, SP_RULES, logical_to_spec, rules_for_shape,
)


def _abstract_mesh(shape, names):
    # shape-only stand-in mesh: rules only read axis names and sizes.
    from jax.sharding import AbstractMesh
    return AbstractMesh(shape, names)


@pytest.fixture(scope="module")
def mesh():
    return _abstract_mesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def pod_mesh():
    return _abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def spec(axes, shape, mesh, rules=DEFAULT_RULES):
    return logical_to_spec(axes, shape, mesh, rules)


def test_mlp_weight_2d_sharded(mesh):
    # yi-9b w_ff: FSDP on embed (data), TP on mlp (model)
    assert spec(("embed", "mlp"), (4096, 11008), mesh) == P("data", "model")


def test_vocab_fallback_whisper(mesh):
    # whisper vocab 51866 does not divide 16 -> replicate vocab, FSDP embed
    assert spec(("vocab", "embed"), (51866, 1280), mesh) == P(None, "data")
    # llama4 vocab divides -> vocab on model
    assert spec(("vocab", "embed"), (202048, 5120), mesh) == P("model", "data")


def test_mqa_kv_fallback_granite(mesh):
    # granite kv=1: kv_heads can't shard; head_dim 128 picks up model
    assert spec(
        ("embed", "kv_heads", "head_dim"), (6144, 1, 128), mesh
    ) == P("data", None, "model")


def test_qwen_heads_fallback(mesh):
    # 40 heads don't divide 16 -> head_dim carries TP
    assert spec(
        ("embed", "heads", "head_dim"), (5120, 40, 128), mesh
    ) == P("data", None, "model")
    # 32 heads divide -> heads carry TP, head_dim replicated
    assert spec(
        ("embed", "heads", "head_dim"), (4096, 32, 128), mesh
    ) == P("data", "model")


def test_batch_composite_pod_axis(pod_mesh):
    assert spec(("batch", "seq"), (256, 4096), pod_mesh) == P(("pod", "data"))
    # batch=1 (long_500k) can't shard -> replicated
    assert spec(("batch", "seq"), (1, 524288), pod_mesh,
                DECODE_RULES) == P()


def test_decode_cache_seq_fallback(mesh):
    # h2o kv=8 on 16-way model: cache timeline carries TP (split-KV)
    assert spec(
        ("batch", "kv_heads", "cache_seq", "head_dim"),
        (128, 8, 32768, 80), mesh, DECODE_RULES,
    ) == P("data", None, "model")
    # zamba shared kv=32: heads carry TP, timeline replicated
    assert spec(
        ("batch", "kv_heads", "cache_seq", "head_dim"),
        (1, 32, 524288, 64), mesh, DECODE_RULES,
    ) == P(None, "model")


def test_experts_on_model(mesh):
    assert spec(
        ("experts", "embed", "mlp"), (64, 2048, 1408), mesh
    ) == P("model", "data")


def test_no_axis_used_twice(mesh):
    # embed takes data; a second embed-like dim must not also take data
    s = spec(("embed", "embed"), (1280, 4096), mesh)
    used = [a for a in s if a is not None]
    assert len(set(used)) == len(used)


def test_sp_rules_shard_seq(mesh):
    assert spec(("batch", "seq", "embed_act"), (32, 32768, 4096), mesh,
                SP_RULES) == P("data", "model")
    # whisper frames 1500 don't divide -> replicate
    assert spec(("batch", "frames", None), (32, 1500, 1280), mesh,
                SP_RULES) == P("data")


def test_rules_for_shape():
    assert rules_for_shape("train_4k") is DEFAULT_RULES
    assert rules_for_shape("prefill_32k") is SP_RULES
    assert rules_for_shape("decode_32k") is DECODE_RULES
    assert rules_for_shape("long_500k") is DECODE_RULES
