"""Logical-axis sharding rules: divisibility fallback, axis-reuse, priority.

Mesh objects here are abstract (built from the 1 real device is impossible
for 16x16) — ``jax.sharding.AbstractMesh`` carries only shape/axis names,
which is all ``spec_for`` consults.
"""

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import SERVE_RULES, TRAIN_RULES, spec_for


def _mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def test_batch_falls_back_without_pod():
    spec = spec_for(("batch", None), (256, 128), _mesh(), TRAIN_RULES)
    assert spec == P("data")


def test_batch_uses_pod_and_data_when_present():
    spec = spec_for(("batch", None), (256, 128), _mesh(True), TRAIN_RULES)
    assert spec == P(("pod", "data"))


def test_divisibility_fallback_to_replication():
    # 40 heads % 16 != 0 -> replicated; flattened 5120 projection dim shards.
    assert spec_for(("heads",), (40,), _mesh(), TRAIN_RULES) == P()
    assert spec_for(("embed", "qkv"), (5120, 5120), _mesh(), TRAIN_RULES) == P("data", "model")


def test_axis_reuse_forbidden():
    # Two dims competing for "model": priority order wins, second replicates.
    spec = spec_for(("qkv", "mlp"), (512, 512), _mesh(), TRAIN_RULES)
    assert spec in (P("model"), P("model", None))  # mlp loses, replicated


def test_kv_cache_priority():
    # kv_heads (8) not divisible by model=16 -> kv_seq takes "model".
    spec = spec_for(
        ("layers", "batch", "kv_seq", "kv_heads", None),
        (40, 128, 32768, 8, 128), _mesh(), SERVE_RULES,
    )
    assert spec == P(None, "data", "model") or spec == P(None, "data", "model", None)
    # kv_heads 32 IS divisible -> kv_heads wins "model", kv_seq replicates.
    spec2 = spec_for(
        ("layers", "batch", "kv_seq", "kv_heads", None),
        (40, 128, 32768, 32, 128), _mesh(), SERVE_RULES,
    )
    assert spec2 == P(None, "data", None, "model")


def test_vocab_on_model():
    assert spec_for(("vocab", "embed"), (49664, 4096), _mesh(), TRAIN_RULES) == P("model", "data")


def test_unknown_logical_axis_replicates():
    assert spec_for(("nonexistent",), (64,), _mesh(), TRAIN_RULES) == P()


def test_serve_rules_replicate_weights_over_data():
    assert spec_for(("embed", "qkv"), (4096, 4096), _mesh(), SERVE_RULES) == P(None, "model")
