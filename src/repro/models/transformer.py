"""Decoder-only transformer LM (dense / MoE / VLM backbone).

Layer-stacked parameters (leading ``num_layers`` dim) consumed by
``jax.lax.scan`` — keeps the HLO size O(1) in depth, which matters both for
pod-scale compile times and for this container's CPU compiles of 126-layer
models.  Remat policy wraps the scan body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.moe import expert_specs, moe_apply, moe_init


def _attn_spec(cfg: ModelConfig) -> L.AttnParamsSpec:
    return L.AttnParamsSpec(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
    )


def layer_init(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "ln_attn": L.rmsnorm_init(cfg.d_model, dt),
        "attn": L.attention_init(k1, _attn_spec(cfg), dt),
        "ln_mlp": L.rmsnorm_init(cfg.d_model, dt),
    }
    if cfg.moe is not None:
        p["moe"] = moe_init(k2, cfg.d_model, cfg.moe, dt)
    else:
        p["mlp"] = L.mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.mlp, dt)
    return p


def layer_apply(p, x, cfg: ModelConfig, *, positions, sharder=None,
                cache=None, cache_pos=None, layer=None, causal=True,
                window=None):
    """Pre-norm block: x + attn(ln(x)); x + mlp(ln(x)).  Returns
    (x, new_cache, aux).  With ``layer``, ``cache`` is every layer's cache
    stacked and is updated in place (``attention_apply``)."""
    dt = jnp.dtype(cfg.dtype)
    h = L.rmsnorm(p["ln_attn"], x, cfg.norm_eps)
    attn_out, new_cache = L.attention_apply(
        p["attn"], h, spec=_attn_spec(cfg), dtype=dt,
        rope_theta=cfg.rope_theta, positions=positions, causal=causal,
        window=window, cache=cache, cache_pos=cache_pos, layer=layer,
        sharder=sharder, attn_chunk=cfg.attn_chunk,
        causal_skip=cfg.attn_causal_skip,
    )
    x = x + attn_out
    h = L.rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        mlp_out, aux = moe_apply(p["moe"], h, cfg.moe, dt, sharder=sharder)
    else:
        mlp_out = L.mlp_apply(p["mlp"], h, cfg.mlp, dt, sharder=sharder)
    x = x + mlp_out
    if sharder is not None:
        x = sharder.act_btd(x)
    return x, new_cache, aux


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------


def lm_init(key, cfg: ModelConfig):
    """Weights with the layers stacked on a leading axis.  The layers are
    drawn under ``vmap``, which writes the stacked arrays directly: no
    per-layer copy is ever live beside them (run it under ``jax.jit`` —
    ``build_model`` does — so the init is one program, not one per op)."""
    keys = jax.random.split(key, cfg.num_layers + 3)
    dt = jnp.dtype(cfg.param_dtype)
    stacked = jax.vmap(lambda k: layer_init(k, cfg))(keys[: cfg.num_layers])
    p = {
        "embed": L.embedding_init(keys[-1], cfg.vocab_size, cfg.d_model, dt),
        "layers": stacked,
        "final_norm": L.rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        p["head"] = {
            "w": jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab_size), dt)
            * (1.0 / cfg.d_model**0.5)
        }
    if cfg.vlm is not None:
        p["patch_proj"] = L.dense_init(keys[-3], cfg.d_model, cfg.d_model, dt)
    return p


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {cfg.remat!r}")


def _embed_inputs(p, batch, cfg: ModelConfig, dt, sharder):
    """tokens (+ patch_embeds for VLM) -> (B, S, d) embeddings."""
    x = L.embed(p["embed"], batch["tokens"], dt)
    if cfg.vlm is not None:
        patches = L.dense(p["patch_proj"], batch["patch_embeds"].astype(dt), dt)
        x = jnp.concatenate([patches, x], axis=1)  # vision prefix
    if sharder is not None:
        x = sharder.act_btd(x)
    return x


def lm_forward(p, batch, cfg: ModelConfig, *, sharder=None, window=None,
               return_cache=False):
    """Train/prefill forward: full-sequence causal attention.

    Returns (logits, caches, aux_mean).  ``caches`` are stacked (L, ...)
    when return_cache (prefill), else None.
    """
    dt = jnp.dtype(cfg.dtype)
    x = _embed_inputs(p, batch, cfg, dt, sharder)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(carry, layer_p):
        x, aux = carry
        x, cache, a = layer_apply(
            layer_p, x, cfg, positions=positions, sharder=sharder, window=window
        )
        out = cache if return_cache else None
        return (x, aux + a), out

    if cfg.scan_layers and cfg.remat_group > 1 and not return_cache:
        # grouped remat: only every g-th layer boundary is saved; the inner
        # scan recomputes through the group on the backward pass.  Cuts the
        # saved-activation footprint by g× (needed for the 340B/405B cells).
        g = cfg.remat_group
        assert cfg.num_layers % g == 0, "remat_group must divide num_layers"
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((cfg.num_layers // g, g) + a.shape[1:]),
            p["layers"],
        )

        def inner(carry, layer_p):
            out, _ = body(carry, layer_p)  # body unwrapped: one remat level
            return out, None

        def group_body(carry, group_p):
            carry, _ = jax.lax.scan(inner, carry, group_p)
            return carry, None

        group_body = _remat_wrap(group_body, cfg)
        (x, aux), caches = jax.lax.scan(
            group_body, (x, jnp.zeros((), jnp.float32)), grouped
        )
    elif cfg.scan_layers:
        body = _remat_wrap(body, cfg)
        (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        p["layers"])
    else:
        body = _remat_wrap(body, cfg)
        aux = jnp.zeros((), jnp.float32)
        caches_list = []
        for i in range(cfg.num_layers):
            layer_p = jax.tree_util.tree_map(lambda q, i=i: q[i], p["layers"])
            (x, aux), c = body((x, aux), layer_p)
            caches_list.append(c)
        caches = (
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches_list)
            if return_cache else None
        )

    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    head = p["head"] if "head" in p else {"w": p["embed"]["table"].T}
    logits = L.unembed(head, x, dt)
    if sharder is not None:
        logits = sharder.logits(logits)
    return logits, caches, aux / cfg.num_layers


def lm_init_cache(cfg: ModelConfig, batch_size: int, max_len: int, *,
                  window=None):
    S = min(max_len, window) if window is not None else max_len
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (cfg.num_layers, batch_size, S, hk, hd)
    dt = jnp.dtype(cfg.dtype)
    if cfg.kv_quant:
        sshape = (cfg.num_layers, batch_size, S, hk, 1)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def lm_decode_step(p, cache, batch, cfg: ModelConfig, *, sharder=None,
                   window=None):
    """One decode step: ``batch = {tokens: (B, 1), pos: scalar int32}``.
    Returns (logits (B, 1, V), new_cache)."""
    dt = jnp.dtype(cfg.dtype)
    x = L.embed(p["embed"], batch["tokens"], dt)
    if sharder is not None:
        x = sharder.act_btd(x)
    pos = batch["pos"]
    if pos.ndim == 0:
        positions = pos[None].astype(jnp.int32)         # (t=1,) synchronous
    else:
        positions = pos[:, None].astype(jnp.int32)      # (B, t=1) per-slot

    def body(carry, layer_in):
        x, aux = carry
        layer_p, cache_l = layer_in
        x, new_cache_l, a = layer_apply(
            layer_p, x, cfg, positions=positions, sharder=sharder,
            cache=cache_l, cache_pos=pos, window=window,
        )
        return (x, aux + a), new_cache_l

    if cfg.scan_layers:
        # the cache rides in the carry: emitted as scan outputs instead, the
        # new cache would be a second whole copy of it beside the donated
        # one.  Each layer writes its new K/V rows into the stacked carry in
        # place and attends over its own layer of it; only the int8 cache
        # still takes its layer's slab out and writes it back whole
        def carried(carry, layer_in):
            x, aux, cache = carry
            layer_p, i = layer_in
            if "k_scale" not in cache:
                x, cache, a = layer_apply(
                    layer_p, x, cfg, positions=positions, sharder=sharder,
                    cache=cache, cache_pos=pos, layer=i, window=window,
                )
                return (x, aux + a, cache), None
            cache_l = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, keepdims=False),
                cache)
            (x, aux), new_l = body((x, aux), (layer_p, cache_l))
            cache = jax.tree_util.tree_map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
                cache, new_l)
            return (x, aux, cache), None

        (x, _, new_cache), _ = jax.lax.scan(
            carried, (x, jnp.zeros((), jnp.float32), cache),
            (p["layers"], jnp.arange(cfg.num_layers)),
        )
    else:
        outs = []
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            sel = lambda q, i=i: q[i]  # bind i: late-binding closure pitfall
            (x, aux), c = body(
                (x, aux),
                (jax.tree_util.tree_map(sel, p["layers"]),
                 jax.tree_util.tree_map(sel, cache)),
            )
            outs.append(c)
        new_cache = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    head = p["head"] if "head" in p else {"w": p["embed"]["table"].T}
    logits = L.unembed(head, x, dt)
    if sharder is not None:
        logits = sharder.logits(logits)
    return logits, new_cache


def lm_loss(p, batch, cfg: ModelConfig, *, sharder=None, aux_weight=0.01):
    logits, _, aux = lm_forward(p, batch, cfg, sharder=sharder)
    labels = batch["labels"]
    if cfg.vlm is not None:
        # vision prefix carries no labels
        pad = jnp.full(
            (labels.shape[0], cfg.vlm.num_patches), -100, labels.dtype
        )
        labels = jnp.concatenate([pad, labels], axis=1)
    loss = L.cross_entropy(logits, labels)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# --------------------------------------------------------------------------
# sharding rules for the param tree (mirrors lm_init's structure)
# --------------------------------------------------------------------------


def lm_param_rules(cfg: ModelConfig):
    """Rules pytree (same structure as params) for Sharder.spec.

    Leading dim of every stacked layer leaf is the layer dim (never
    sharded); weights shard output-column over "model" and, under FSDP,
    input-row over the data axes.
    """
    attn = {
        "wq": [None, ["fsdp"], "model", None],
        "wk": [None, ["fsdp"], "model", None],
        "wv": [None, ["fsdp"], "model", None],
        "wo": [None, "model", None, ["fsdp"]],
    }
    if cfg.qkv_bias:
        attn.update({
            "bq": [None, "model", None],
            "bk": [None, "model", None],
            "bv": [None, "model", None],
        })
    layer = {
        "ln_attn": {"scale": [None, None]},
        "ln_mlp": {"scale": [None, None]},
        "attn": attn,
    }
    if cfg.moe is not None:
        moe_rules = {
            k: [None] + v for k, v in expert_specs(None, cfg.moe).items()
        }
        if cfg.moe.num_shared_experts:
            moe_rules["shared"] = {
                "w_gate": [None, ["fsdp"], "model"],
                "w_up": [None, ["fsdp"], "model"],
                "w_down": [None, "model", ["fsdp"]],
                "gate": [None, None, None],
            }
        layer["moe"] = moe_rules
    else:
        mlp = {
            "w_up": [None, ["fsdp"], "model"],
            "w_down": [None, "model", ["fsdp"]],
        }
        if cfg.mlp == "swiglu":
            mlp["w_gate"] = [None, ["fsdp"], "model"]
        layer["mlp"] = mlp
    rules = {
        "embed": {"table": [["fsdp"], "model"]},
        "layers": layer,
        "final_norm": {"scale": [None]},
    }
    if not cfg.tie_embeddings:
        rules["head"] = {"w": [["fsdp"], "model"]}
    if cfg.vlm is not None:
        rules["patch_proj"] = {"w": [["fsdp"], "model"]}
    return rules


def lm_cache_rules(cfg: ModelConfig | None = None, model_axis_size: int = 16):
    """KV-cache sharding: heads over the model axis when they divide it
    (zamba 32, olmoe/qwen2moe 16); otherwise the cache *sequence* dim is
    sharded (flash-decode-style partial softmax — GSPMD reduces the tiny
    (B,H,t) statistics across shards).  kv=8/20 archs take the seq path."""
    if cfg is not None and cfg.num_kv_heads % model_axis_size == 0:
        rule = [None, "batch", None, "model", None]
    else:
        rule = [None, "batch", "model", None, None]
    rules = {"k": list(rule), "v": list(rule)}
    if cfg is not None and cfg.kv_quant:
        srule = rule[:-1] + [None]
        rules["k_scale"] = list(srule)
        rules["v_scale"] = list(srule)
    return rules
