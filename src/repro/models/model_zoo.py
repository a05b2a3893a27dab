"""Unified model API over the 10 assigned architectures.

``build(cfg)`` returns a :class:`ModelApi` with a family-independent surface:

- ``params_def``                  declarative Param tree (``materialize``
                                  derives the parameters from it)
- ``loss(params, batch)``         full train forward + masked CE (+ MoE aux)
- ``prefill(params, batch)``      -> (last logits, decode cache)
- ``decode(params, cache, token, pos)`` -> (logits, cache)   [serve_step]
- ``train_inputs/prefill_inputs/decode_inputs(shape)``  TensorSpec trees
  (shape, dtype, logical axes) of each input
- ``cache_spec(shape)``           TensorSpec tree matching the decode cache

The serving demo (examples/serve_energy.py) and the tests build batches
from these specs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf
from repro.models import xlstm as xlstm_mod
from repro.models.common import cross_entropy_loss

Array = jax.Array

MOE_AUX_WEIGHT = 0.01

#: Source length for enc-dec / cross-attention memories in decode cells
#: (a ~30 s utterance; prefill/train use the full assigned seq_len).
DECODE_SRC_LEN = 4096


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    shape: tuple[int, ...]
    dtype: Any
    axes: tuple[str | None, ...]


def is_spec(x) -> bool:
    """True for ``TensorSpec`` leaves (tree-traversal predicate)."""
    return isinstance(x, TensorSpec)


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ArchConfig
    params_def: Any
    loss: Callable          # (params, batch) -> (loss, metrics)
    prefill: Callable       # (params, batch) -> (logits, cache)
    decode: Callable        # (params, cache, token, pos) -> (logits, cache)
    train_inputs: Callable  # (ShapeConfig) -> TensorSpec tree
    prefill_inputs: Callable
    decode_inputs: Callable  # (ShapeConfig) -> (token/pos specs)
    cache_spec: Callable     # (ShapeConfig) -> TensorSpec tree


def _tok(b: int, s: int) -> TensorSpec:
    return TensorSpec((b, s), jnp.int32, ("batch", None))


def _compute_dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.compute_dtype)


def _kv_spec(cfg: ArchConfig, layers: int, b: int, s: int) -> TensorSpec:
    return TensorSpec(
        (layers, b, s, cfg.num_kv_heads, cfg.head_dim),
        _compute_dtype(cfg),
        ("layers", "batch", "kv_seq", "kv_heads", None),
    )


# ---------------------------------------------------------------------------
# Family builders
# ---------------------------------------------------------------------------


def _build_decoder(cfg: ArchConfig) -> ModelApi:
    """dense / moe / vlm."""
    vlm = cfg.family == "vlm"
    n_scan = cfg.num_layers - (1 if (cfg.family == "moe" and cfg.first_dense) else 0)

    def loss(params, batch):
        prefix = batch.get("patches") if vlm else None
        labels = batch["labels"]
        if vlm:
            b, p = labels.shape[0], cfg.frontend_tokens
            pad = jnp.full((b, p), -1, jnp.int32)
            labels = jnp.concatenate([pad, labels], axis=1)
        if cfg.ce_chunk > 0:
            h, aux = tf.decoder_hidden_states(
                params, batch["tokens"], cfg, prefix_embeds=prefix
            )
            from repro.models.common import chunked_lm_loss, rms_norm

            h = rms_norm(h, params["ln_f"], cfg.norm_eps)
            w = params["unembed"] if "unembed" in params else params["embed"].T
            l, metrics = chunked_lm_loss(
                h, w, labels, cfg.vocab_size, cfg.ce_chunk,
                logit_softcap=cfg.logit_softcap,
            )
        else:
            logits, aux = tf.decoder_train(params, batch["tokens"], cfg, prefix_embeds=prefix)
            l, metrics = cross_entropy_loss(logits, labels, cfg.vocab_size)
        if cfg.family == "moe":
            l = l + MOE_AUX_WEIGHT * aux
            metrics["moe_aux"] = aux
        metrics["loss"] = l
        return l, metrics

    def prefill(params, batch):
        prefix = batch.get("patches") if vlm else None
        return tf.decoder_prefill(params, batch["tokens"], cfg, prefix_embeds=prefix)

    def decode(params, cache, token, pos):
        return tf.decoder_decode(params, cache, token, pos, cfg)

    def train_inputs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        if vlm:
            p = cfg.frontend_tokens
            return {
                "patches": TensorSpec((b, p, cfg.frontend_dim), _compute_dtype(cfg), ("batch", None, None)),
                "tokens": _tok(b, s - p),
                "labels": _tok(b, s - p),
            }
        return {"tokens": _tok(b, s), "labels": _tok(b, s)}

    def prefill_inputs(shape: ShapeConfig):
        spec = train_inputs(shape)
        spec.pop("labels")
        return spec

    def decode_inputs(shape: ShapeConfig):
        return {
            "token": _tok(shape.global_batch, 1),
            "pos": TensorSpec((), jnp.int32, ()),
        }

    def cache_spec(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        if cfg.kv_cache_dtype == "int8":
            kv = TensorSpec(
                (n_scan, b, s, cfg.num_kv_heads, cfg.head_dim), jnp.int8,
                ("layers", "batch", "kv_seq", "kv_heads", None),
            )
            sc = TensorSpec(
                (n_scan, b, s, cfg.num_kv_heads), jnp.bfloat16,
                ("layers", "batch", "kv_seq", "kv_heads"),
            )
            spec = {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc}
        else:
            spec = {"k": _kv_spec(cfg, n_scan, b, s), "v": _kv_spec(cfg, n_scan, b, s)}
        if cfg.family == "moe" and cfg.first_dense:
            kv0 = TensorSpec(
                (b, s, cfg.num_kv_heads, cfg.head_dim),
                _compute_dtype(cfg),
                ("batch", "kv_seq", "kv_heads", None),
            )
            spec["k0"] = kv0
            spec["v0"] = kv0
        return spec

    return ModelApi(
        cfg, tf.decoder_params(cfg), loss, prefill, decode,
        train_inputs, prefill_inputs, decode_inputs, cache_spec,
    )


def _build_hybrid(cfg: ArchConfig) -> ModelApi:
    napp = tf._n_attn_points(cfg)

    def loss(params, batch):
        logits, _ = tf.hybrid_train(params, batch["tokens"], cfg)
        l, metrics = cross_entropy_loss(logits, batch["labels"], cfg.vocab_size)
        return l, metrics

    def prefill(params, batch):
        return tf.hybrid_prefill(params, batch["tokens"], cfg)

    def decode(params, cache, token, pos):
        return tf.hybrid_decode(params, cache, token, pos, cfg)

    def train_inputs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        return {"tokens": _tok(b, s), "labels": _tok(b, s)}

    def prefill_inputs(shape: ShapeConfig):
        return {"tokens": _tok(shape.global_batch, shape.seq_len)}

    def decode_inputs(shape: ShapeConfig):
        return {
            "token": _tok(shape.global_batch, 1),
            "pos": TensorSpec((), jnp.int32, ()),
        }

    def cache_spec(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        l = cfg.num_layers
        return {
            "attn_k": TensorSpec(
                (napp, b, s, cfg.num_kv_heads, cfg.head_dim), _compute_dtype(cfg),
                (None, "batch", "kv_seq", "kv_heads", None),
            ),
            "attn_v": TensorSpec(
                (napp, b, s, cfg.num_kv_heads, cfg.head_dim), _compute_dtype(cfg),
                (None, "batch", "kv_seq", "kv_heads", None),
            ),
            "ssm_h": TensorSpec(
                (l, b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32,
                ("layers", "batch", "heads", None, None),
            ),
            "conv": TensorSpec(
                (l, b, cfg.ssm_conv - 1, cfg.d_inner), _compute_dtype(cfg),
                ("layers", "batch", None, "mlp"),
            ),
        }

    return ModelApi(
        cfg, tf.hybrid_params(cfg), loss, prefill, decode,
        train_inputs, prefill_inputs, decode_inputs, cache_spec,
    )


def _build_xlstm(cfg: ArchConfig) -> ModelApi:
    def loss(params, batch):
        logits, _ = xlstm_mod.xlstm_train(params, batch["tokens"], cfg)
        l, metrics = cross_entropy_loss(logits, batch["labels"], cfg.vocab_size)
        return l, metrics

    def prefill(params, batch):
        return xlstm_mod.xlstm_prefill(params, batch["tokens"], cfg)

    def decode(params, cache, token, pos):
        return xlstm_mod.xlstm_decode(params, cache, token, pos, cfg)

    def train_inputs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        return {"tokens": _tok(b, s), "labels": _tok(b, s)}

    def prefill_inputs(shape: ShapeConfig):
        return {"tokens": _tok(shape.global_batch, shape.seq_len)}

    def decode_inputs(shape: ShapeConfig):
        return {
            "token": _tok(shape.global_batch, 1),
            "pos": TensorSpec((), jnp.int32, ()),
        }

    def cache_spec(shape: ShapeConfig):
        b = shape.global_batch
        pairs = cfg.num_layers // 2
        h = cfg.num_heads
        p_m = (2 * cfg.d_model) // h     # mLSTM head dim
        p_s = cfg.d_model // h           # sLSTM head dim
        s_state = TensorSpec((pairs, b, h, p_s), jnp.float32, ("layers", "batch", "heads", None))
        return {
            "m": TensorSpec(
                (pairs, b, h, p_m + 1, p_m), jnp.float32,
                ("layers", "batch", "heads", None, None),
            ),
            "s_c": s_state, "s_n": s_state, "s_m": s_state, "s_h": s_state,
        }

    return ModelApi(
        cfg, xlstm_mod.xlstm_params(cfg), loss, prefill, decode,
        train_inputs, prefill_inputs, decode_inputs, cache_spec,
    )


def _build_encdec(cfg: ArchConfig) -> ModelApi:
    def loss(params, batch):
        logits, _ = encdec_mod.encdec_train(params, batch["src_embeds"], batch["tokens"], cfg)
        l, metrics = cross_entropy_loss(logits, batch["labels"], cfg.vocab_size)
        return l, metrics

    def prefill(params, batch):
        return encdec_mod.encdec_prefill(params, batch["src_embeds"], batch["tokens"], cfg)

    def decode(params, cache, token, pos):
        return encdec_mod.encdec_decode(params, cache, token, pos, cfg)

    def train_inputs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        return {
            "src_embeds": TensorSpec((b, s, cfg.frontend_dim), _compute_dtype(cfg), ("batch", None, None)),
            "tokens": _tok(b, s),
            "labels": _tok(b, s),
        }

    def prefill_inputs(shape: ShapeConfig):
        spec = train_inputs(shape)
        spec.pop("labels")
        return spec

    def decode_inputs(shape: ShapeConfig):
        return {
            "token": _tok(shape.global_batch, 1),
            "pos": TensorSpec((), jnp.int32, ()),
        }

    def cache_spec(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        src = min(s, DECODE_SRC_LEN)
        l = cfg.num_layers
        return {
            "k": _kv_spec(cfg, l, b, s),
            "v": _kv_spec(cfg, l, b, s),
            "cross_k": _kv_spec(cfg, l, b, src),
            "cross_v": _kv_spec(cfg, l, b, src),
        }

    return ModelApi(
        cfg, encdec_mod.encdec_params(cfg), loss, prefill, decode,
        train_inputs, prefill_inputs, decode_inputs, cache_spec,
    )


_BUILDERS = {
    "dense": _build_decoder,
    "moe": _build_decoder,
    "vlm": _build_decoder,
    "hybrid": _build_hybrid,
    "ssm": _build_xlstm,
    "encdec": _build_encdec,
}


def build(cfg: ArchConfig) -> ModelApi:
    """Construct the ``ModelApi`` for a config's model family."""
    try:
        return _BUILDERS[cfg.family](cfg)
    except KeyError:
        raise ValueError(f"unknown family {cfg.family!r} for {cfg.name}") from None


#: cache entries that grow along their KV-sequence axis (axis index), per
#: family.  Cross-attention KV and recurrent states never grow.
_GROWABLE = {
    "dense": {"k": 2, "v": 2, "k0": 1, "v0": 1, "k_scale": 2, "v_scale": 2},
    "moe": {"k": 2, "v": 2, "k0": 1, "v0": 1, "k_scale": 2, "v_scale": 2},
    "vlm": {"k": 2, "v": 2, "k_scale": 2, "v_scale": 2},
    "hybrid": {"attn_k": 2, "attn_v": 2},
    "encdec": {"k": 2, "v": 2},
    "ssm": {},
}


def extend_cache(api: ModelApi, cache: dict, extra: int) -> dict:
    """Grow the decode cache by ``extra`` KV slots (zeros; masked by pos).

    A prefill over S tokens returns caches with exactly S slots — decoding
    N further tokens needs S+N.  Zero padding is safe: decode attention
    masks by ``lengths = pos + 1``, so unwritten slots are never attended.
    """
    if extra <= 0:
        return cache
    grow = _GROWABLE[api.cfg.family]
    out = dict(cache)
    for name, axis in grow.items():
        if name not in out:
            continue
        x = out[name]
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, extra)
        out[name] = jnp.pad(x, pad)
    return out
