"""Unified model API: every assigned architecture behind one surface.

``build_model(cfg)`` returns a :class:`Model` whose members are pure
functions — ready for ``jax.jit`` with explicit shardings (dry-run), the
training loop, and the serving engine's device handler table (prefill and
decode registered as HAM device handlers sharing the cache payload spec).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import transformer as T
from repro.models import whisper as W
from repro.models import xlstm as X
from repro.models import zamba2 as Z
from repro.models.config import ModelConfig, ShapeCell


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    loss: Callable           # (params, batch, sharder=None) -> (loss, metrics)
    forward: Callable        # (params, batch, sharder=None) -> logits
    prefill: Callable        # (params, batch, sharder=None) -> (logits, cache)
    decode_step: Callable    # (params, cache, batch, sharder=None) -> (logits, cache)
    init_cache: Callable     # (batch_size, max_len, window=None) -> cache
    param_rules: Callable    # () -> rules pytree (Sharder format)
    cache_rules: Callable    # () -> rules pytree for the cache
    input_specs: Callable    # (cell) -> batch pytree of ShapeDtypeStruct
    has_decode: bool = True


def _i32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _f32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _token_specs(cfg: ModelConfig, cell: ShapeCell):
    B, S = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        batch = {"tokens": _i32((B, S)), "labels": _i32((B, S))}
    elif cell.kind == "prefill":
        batch = {"tokens": _i32((B, S))}
    else:  # decode: one new token, cache covers seq_len
        batch = {"tokens": _i32((B, 1)), "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.vlm is not None and cell.kind != "decode":
        n_text = S - cfg.vlm.num_patches
        batch["tokens"] = _i32((B, n_text))
        if "labels" in batch:
            batch["labels"] = _i32((B, n_text))
        batch["patch_embeds"] = _f32((B, cfg.vlm.num_patches, cfg.d_model))
    if cfg.encdec is not None and cell.kind != "decode":
        batch["frames"] = _f32((B, cfg.encdec.encoder_frames, cfg.d_model))
    return batch


def _generic_loss(forward_fn):
    def loss(params, batch, sharder=None, aux_weight=0.01):
        logits, _, aux = forward_fn(params, batch, sharder=sharder)
        labels = batch["labels"]
        if logits.shape[1] != labels.shape[1]:  # vision prefix (VLM)
            pad = jnp.full(
                (labels.shape[0], logits.shape[1] - labels.shape[1]),
                -100, labels.dtype,
            )
            labels = jnp.concatenate([pad, labels], axis=1)
        ce = L.cross_entropy(logits, labels)
        return ce + aux_weight * aux, {"ce": ce, "aux": aux}

    return loss


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm"):
        fwd = lambda p, b, sharder=None: T.lm_forward(p, b, cfg, sharder=sharder)

        def prefill(p, b, sharder=None):
            logits, cache, _ = T.lm_forward(p, b, cfg, sharder=sharder,
                                            return_cache=True)
            return logits, cache

        return Model(
            cfg=cfg,
            init=jax.jit(lambda key: T.lm_init(key, cfg)),
            loss=_generic_loss(fwd),
            forward=lambda p, b, sharder=None: fwd(p, b, sharder)[0],
            prefill=prefill,
            decode_step=lambda p, c, b, sharder=None: T.lm_decode_step(
                p, c, b, cfg, sharder=sharder),
            init_cache=lambda bs, ml, window=None: T.lm_init_cache(
                cfg, bs, ml, window=window),
            param_rules=lambda: T.lm_param_rules(cfg),
            cache_rules=lambda: T.lm_cache_rules(cfg),
            input_specs=lambda cell: _token_specs(cfg, cell),
        )

    if cfg.family == "ssm":  # xLSTM
        fwd = lambda p, b, sharder=None: X.xlstm_forward(p, b, cfg, sharder=sharder)

        def prefill(p, b, sharder=None):
            logits, states, _ = X.xlstm_forward(p, b, cfg, sharder=sharder,
                                                return_cache=True)
            mst, sst = states
            return logits, {"mlstm": mst, "slstm": sst}

        def cache_rules():
            m_rule = (
                [None, None, "batch", None, "model", None],   # C
                [None, None, "batch", None, "model"],         # n
                [None, None, "batch", None],                  # m
                [None, None, "batch", None, "model"],         # conv
            )
            s_rule = (
                [None, None, "batch", "model"],
                [None, None, "batch", "model"],
                [None, None, "batch", "model"],
                [None, None, "batch", "model"],
                [None, None, "batch", None, "model"],
            )
            return {"mlstm": m_rule, "slstm": s_rule}

        return Model(
            cfg=cfg,
            init=lambda key: X.xlstm_init(key, cfg),
            loss=_generic_loss(fwd),
            forward=lambda p, b, sharder=None: fwd(p, b, sharder)[0],
            prefill=prefill,
            decode_step=lambda p, c, b, sharder=None: X.xlstm_decode_step(
                p, c, b, cfg, sharder=sharder),
            init_cache=lambda bs, ml, window=None: X.xlstm_init_cache(cfg, bs, ml),
            param_rules=lambda: X.xlstm_param_rules(cfg),
            cache_rules=cache_rules,
            input_specs=lambda cell: _token_specs(cfg, cell),
        )

    if cfg.family == "hybrid":  # zamba2
        fwd = lambda p, b, sharder=None: Z.zamba2_forward(p, b, cfg, sharder=sharder)

        def prefill(p, b, sharder=None):
            logits, states, _ = Z.zamba2_forward(p, b, cfg, sharder=sharder,
                                                 return_cache=True)
            mst, kv = states
            return logits, {"mamba": mst, "attn_kv": kv}

        def cache_rules():
            return {
                "mamba": (
                    [None, None, "batch", "model", None, None],  # h
                    [None, None, "batch", None, "model"],        # conv
                ),
                "attn_kv": {
                    "k": [None, "batch", None, "model", None],
                    "v": [None, "batch", None, "model", None],
                },
            }

        return Model(
            cfg=cfg,
            init=lambda key: Z.zamba2_init(key, cfg),
            loss=_generic_loss(fwd),
            forward=lambda p, b, sharder=None: fwd(p, b, sharder)[0],
            prefill=prefill,
            decode_step=lambda p, c, b, sharder=None: Z.zamba2_decode_step(
                p, c, b, cfg, sharder=sharder),
            init_cache=lambda bs, ml, window=None: Z.zamba2_init_cache(
                cfg, bs, ml, window=window),
            param_rules=lambda: Z.zamba2_param_rules(cfg),
            cache_rules=cache_rules,
            input_specs=lambda cell: _token_specs(cfg, cell),
        )

    if cfg.family == "audio":  # whisper enc-dec
        fwd = lambda p, b, sharder=None: W.whisper_forward(p, b, cfg, sharder=sharder)

        def prefill(p, b, sharder=None):
            logits, caches, _ = W.whisper_forward(p, b, cfg, sharder=sharder,
                                                  return_cache=True)
            self_c, cross_c = caches
            return logits, {"self": self_c, "cross": cross_c}

        def cache_rules():
            # kv=20 doesn't divide the 16-way model axis -> shard cache seq
            # (self: 32k ✓); cross cache frames=1500 falls back to replicate
            kv = {"k": [None, "batch", ["model"], None, None],
                  "v": [None, "batch", ["model"], None, None]}
            return {"self": kv, "cross": kv}

        return Model(
            cfg=cfg,
            init=lambda key: W.whisper_init(key, cfg),
            loss=_generic_loss(fwd),
            forward=lambda p, b, sharder=None: fwd(p, b, sharder)[0],
            prefill=prefill,
            decode_step=lambda p, c, b, sharder=None: W.whisper_decode_step(
                p, c, b, cfg, sharder=sharder),
            init_cache=lambda bs, ml, window=None: W.whisper_init_cache(cfg, bs, ml),
            param_rules=lambda: W.whisper_param_rules(cfg),
            cache_rules=cache_rules,
            input_specs=lambda cell: _token_specs(cfg, cell),
        )

    raise ValueError(f"unknown family {cfg.family!r}")
