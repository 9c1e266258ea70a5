"""Shared neural building blocks (pure JAX, functional params-as-pytrees).

Conventions:
* ``init_*`` functions return plain dicts of arrays (param_dtype);
* ``apply`` functions cast to the compute dtype at use sites and keep
  normalisation/softmax statistics in float32;
* every function takes an optional :class:`~repro.models.sharding.Sharder`
  and constrains the activations it produces — GSPMD propagates the rest.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _cast(x, dtype):
    return x.astype(dtype) if x.dtype != jnp.dtype(dtype) else x


# --------------------------------------------------------------------------
# initialisers
# --------------------------------------------------------------------------


def dense_init(key, in_dim, out_dim, dtype, *, bias=False, scale=None):
    scale = scale if scale is not None else in_dim**-0.5
    p = {"w": jax.random.normal(key, (in_dim, out_dim), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense(p, x, dtype):
    y = x @ _cast(p["w"], dtype)
    if "b" in p:
        y = y + _cast(p["b"], dtype)
    return y


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------


def rmsnorm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p, x, eps=1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p, x, eps=1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------


def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(head_dim, theta))  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention (reference XLA path; the Pallas kernels mirror this math)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class AttnParamsSpec:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False


def attention_init(key, spec: AttnParamsSpec, dtype):
    ks = jax.random.split(key, 4)
    d, h, hk, hd = spec.d_model, spec.num_heads, spec.num_kv_heads, spec.head_dim
    s = d**-0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h, hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, hk, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, hk, hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (h, hd, d), dtype) * (h * hd) ** -0.5,
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((hk, hd), dtype)
        p["bv"] = jnp.zeros((hk, hd), dtype)
    return p


def _project_qkv(p, x, dtype, x_kv=None):
    xkv = x if x_kv is None else x_kv
    q = jnp.einsum("btd,dhk->bthk", x, _cast(p["wq"], dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, _cast(p["wk"], dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, _cast(p["wv"], dtype))
    if "bq" in p:
        q = q + _cast(p["bq"], dtype)
        k = k + _cast(p["bk"], dtype)
        v = v + _cast(p["bv"], dtype)
    return q, k, v


def gqa_scores_softmax_value(q, k, v, mask, *, q_per_kv):
    """Grouped attention without materialising repeated KV.

    q: (b, t, h, hd) with h = hk * q_per_kv; k, v: (b, s, hk, hd);
    mask: broadcastable to (b, 1, 1, t, s) boolean (True = attend).
    """
    b, t, h, hd = q.shape
    hk = k.shape[2]
    qg = q.reshape(b, t, hk, q_per_kv, hd)
    scores = jnp.einsum("bthgk,bshk->bhgts", qg, k) / np.sqrt(hd)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshk->bthgk", probs, v)
    return out.reshape(b, t, h, hd)


def _quantize_kv(x):
    """Per-(b, t, head) symmetric int8: x (B, t, hk, hd) ->
    (int8 same shape, f32 scale (B, t, hk, 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def chunked_causal_attention(q, k, v, *, q_per_kv, causal=True, window=None,
                             chunk=1024, causal_skip=False):
    """Q-chunked attention: bounds the score tile to (chunk, S) so 32k+
    prefills never materialise the full (S, S) matrix (the XLA-path
    equivalent of the flash kernel's tiling).

    ``causal_skip`` (§Perf lever): each chunk attends only to keys up to its
    own end — the kv extent grows per chunk (statically sliced, so the loop
    is unrolled).  Halves both attention flops and score-tile traffic versus
    the scan-over-full-S baseline.
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    nq = t // chunk
    assert t % chunk == 0, "attn_chunk must divide sequence length"

    if causal and causal_skip and s == t:
        outs = []
        for i in range(nq):
            qc = q[:, i * chunk:(i + 1) * chunk]
            kv_end = (i + 1) * chunk
            kv_start = 0 if window is None else max(0, kv_end - window - chunk)
            mask = causal_mask(chunk, kv_end - kv_start,
                               q_offset=i * chunk - kv_start, window=window)
            outs.append(gqa_scores_softmax_value(
                qc, k[:, kv_start:kv_end], v[:, kv_start:kv_end], mask,
                q_per_kv=q_per_kv,
            ))
        return jnp.concatenate(outs, axis=1)

    qs = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)

    def body(idx, qc):
        offset = idx * chunk
        if causal:
            mask = causal_mask(chunk, s, q_offset=offset, window=window)
        else:
            mask = jnp.ones((1, 1, 1, chunk, s), bool)
        out = gqa_scores_softmax_value(qc, k, v, mask, q_per_kv=q_per_kv)
        return idx + 1, out

    _, outs = jax.lax.scan(body, jnp.int32(0), qs)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, t, h, hd)


def causal_mask(t, s, q_offset=0, window=None):
    """(1,1,1,t,s) boolean; query position i = q_offset + i attends to
    key positions j <= i (and j > i - window when windowed)."""
    qi = jnp.arange(t)[:, None] + q_offset
    kj = jnp.arange(s)[None, :]
    m = kj <= qi
    if window is not None:
        m = m & (kj > qi - window)
    return m[None, None, None]


def attention_apply(
    p,
    x,
    *,
    spec: AttnParamsSpec,
    dtype,
    rope_theta: float | None,
    positions,
    causal: bool = True,
    window: int | None = None,
    cache: dict | None = None,
    cache_pos=None,
    layer=None,
    x_kv=None,
    sharder=None,
    static_cache: bool = False,
    attn_chunk: int | None = None,
    causal_skip: bool = False,
):
    """Full/causal/cross attention with optional KV cache.

    Modes:
    * train/prefill:   cache=None -> attend within x (returns new cache built
                       from k, v when ``return_cache`` via prefill wrapper)
    * decode:          cache={'k','v'} (b, S, hk, hd); the t new tokens are
                       written at ``cache_pos`` and attend over the cache.
    * stacked decode:  cache={'k','v'} (L, b, S, hk, hd) with ``layer`` the
                       traced layer index: only the new rows are written,
                       in place at ``(layer, b, cache_pos)``, attention reads
                       ``cache[layer]``, and the whole stacked cache is
                       returned.  A scan over layers that carries the cache
                       then moves no per-layer slab out and back.
    """
    q, k, v = _project_qkv(p, x, dtype, x_kv=x_kv)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        if x_kv is None:  # self-attention: keys share the query positions
            k = apply_rope(k, positions, rope_theta)
    if sharder is not None:
        q = sharder.constrain(q, ["batch", None, "model", None])
        k = sharder.constrain(k, ["batch", None, "model", None])
        v = sharder.constrain(v, ["batch", None, "model", None])

    new_cache = None
    if cache is not None and static_cache:
        # read-only cache (e.g. cross-attention over precomputed encoder
        # K/V during decode): attend over every slot, no update
        S = cache["k"].shape[1]
        mask = jnp.ones((1, 1, 1, q.shape[1], S), bool)
        out = gqa_scores_softmax_value(
            q, cache["k"], cache["v"], mask,
            q_per_kv=spec.num_heads // spec.num_kv_heads,
        )
        new_cache = cache
    elif cache is not None and "k_scale" in cache:
        # int8-quantised KV cache (kv_quant §Perf lever): values stored as
        # int8 with one f32 scale per (batch, pos, head) vector — 2x less
        # cache HBM traffic than bf16 at <0.5% attention-output error
        S = cache["k"].shape[1]
        kq, ks_ = _quantize_kv(k)
        vq, vs_ = _quantize_kv(v)
        per_slot = hasattr(cache_pos, "ndim") and cache_pos.ndim == 1
        if per_slot:
            bidx = jnp.arange(cache["k"].shape[0])
            new_cache = {
                "k": cache["k"].at[bidx, cache_pos].set(kq[:, 0]),
                "v": cache["v"].at[bidx, cache_pos].set(vq[:, 0]),
                "k_scale": cache["k_scale"].at[bidx, cache_pos].set(ks_[:, 0]),
                "v_scale": cache["v_scale"].at[bidx, cache_pos].set(vs_[:, 0]),
            }
        else:
            dus = jax.lax.dynamic_update_slice
            new_cache = {
                "k": dus(cache["k"], kq, (0, cache_pos, 0, 0)),
                "v": dus(cache["v"], vq, (0, cache_pos, 0, 0)),
                "k_scale": dus(cache["k_scale"], ks_, (0, cache_pos, 0, 0)),
                "v_scale": dus(cache["v_scale"], vs_, (0, cache_pos, 0, 0)),
            }
        ck = new_cache["k"].astype(q.dtype) * new_cache["k_scale"].astype(q.dtype)
        cv = new_cache["v"].astype(q.dtype) * new_cache["v_scale"].astype(q.dtype)
        kj = jnp.arange(S)[None, :]
        qi = positions[..., :, None]
        valid = kj[None] <= qi if qi.ndim == 3 else kj <= qi
        mask = valid[:, None, None] if valid.ndim == 3 else valid[None, None, None]
        out = gqa_scores_softmax_value(
            q, ck, cv, mask, q_per_kv=spec.num_heads // spec.num_kv_heads
        )
    elif cache is not None:
        # positions: (t,) for synchronous batch decode, or (B, t) for
        # per-slot decode (continuous batching in the serving engine);
        # cache slots are linear, or a ring buffer of size S=window for
        # windowed attention (long-context hybrid cells)
        S = cache["k"].shape[-3]
        per_slot = hasattr(cache_pos, "ndim") and cache_pos.ndim == 1
        widx = (cache_pos % S) if window is not None else cache_pos
        lead = () if layer is None else (layer,)
        if per_slot:
            bidx = jnp.arange(cache["k"].shape[-4])

            def write(c, new):
                return c.at[lead + (bidx, widx)].set(new[:, 0])
        else:
            def write(c, new):
                new = new if layer is None else new[None]
                return jax.lax.dynamic_update_slice(
                    c, new, lead + (0, widx, 0, 0))
        new_cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
        ck, cv = new_cache["k"], new_cache["v"]
        if layer is not None:
            ck = jax.lax.dynamic_index_in_dim(ck, layer, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv, layer, keepdims=False)
        kj = jnp.arange(S)[None, :]
        # qi: (t, 1) or (B, t, 1) absolute query positions
        qi = positions[..., :, None]
        if window is None:
            valid = kj[None] <= qi if qi.ndim == 3 else kj <= qi
        else:
            # ring buffer: slot j holds the newest position p ≡ j (mod S);
            # valid iff 0 <= p and within the window
            kj_b = kj[None] if qi.ndim == 3 else kj
            slot_pos = qi - ((qi - kj_b) % S)
            valid = (slot_pos >= 0) & (slot_pos > qi - window)
        # -> broadcastable to (B?, 1, 1, t, S)
        mask = valid[:, None, None] if valid.ndim == 3 else valid[None, None, None]
        out = gqa_scores_softmax_value(q, ck, cv, mask, q_per_kv=spec.num_heads // spec.num_kv_heads)
    else:
        t, s = q.shape[1], k.shape[1]
        qpk = spec.num_heads // spec.num_kv_heads
        if attn_chunk is not None and t > attn_chunk and t % attn_chunk == 0:
            out = chunked_causal_attention(
                q, k, v, q_per_kv=qpk, causal=causal, window=window,
                chunk=attn_chunk, causal_skip=causal_skip,
            )
        else:
            if causal:
                mask = causal_mask(t, s, window=window)
            else:
                mask = jnp.ones((1, 1, 1, t, s), bool)
            out = gqa_scores_softmax_value(q, k, v, mask, q_per_kv=qpk)
        new_cache = {"k": k, "v": v}

    if sharder is not None:
        out = sharder.constrain(out, ["batch", None, "model", None])
    y = jnp.einsum("bthk,hkd->btd", out, _cast(p["wo"], dtype))
    if sharder is not None:
        y = sharder.act_btd(y)
    return y, new_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------


def mlp_init(key, d_model, d_ff, kind, dtype):
    ks = jax.random.split(key, 3)
    s_in = d_model**-0.5
    s_out = d_ff**-0.5
    if kind == "swiglu":
        return {
            "w_gate": jax.random.normal(ks[0], (d_model, d_ff), dtype) * s_in,
            "w_up": jax.random.normal(ks[1], (d_model, d_ff), dtype) * s_in,
            "w_down": jax.random.normal(ks[2], (d_ff, d_model), dtype) * s_out,
        }
    if kind in ("relu2", "gelu"):  # relu2: nemotron-4; gelu: whisper
        return {
            "w_up": jax.random.normal(ks[0], (d_model, d_ff), dtype) * s_in,
            "w_down": jax.random.normal(ks[1], (d_ff, d_model), dtype) * s_out,
        }
    raise ValueError(f"unknown mlp kind {kind!r}")


def mlp_apply(p, x, kind, dtype, sharder=None):
    if kind == "swiglu":
        h = jax.nn.silu(x @ _cast(p["w_gate"], dtype)) * (x @ _cast(p["w_up"], dtype))
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(x @ _cast(p["w_up"], dtype)))
    elif kind == "gelu":
        h = jax.nn.gelu(x @ _cast(p["w_up"], dtype))
    else:
        raise ValueError(kind)
    if sharder is not None:
        h = sharder.constrain(h, ["batch", "seq", "model"])
    y = h @ _cast(p["w_down"], dtype)
    if sharder is not None:
        y = sharder.act_btd(y)
    return y


# --------------------------------------------------------------------------
# embeddings
# --------------------------------------------------------------------------


def embedding_init(key, vocab, d_model, dtype):
    return {"table": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embed(p, tokens, dtype):
    return _cast(p["table"], dtype)[tokens]


def unembed(p_head, x, dtype):
    """x (b, t, d) -> logits (b, t, V); head weight (d, V) vocab-parallel."""
    return x @ _cast(p_head["w"], dtype)


def cross_entropy(logits, labels, *, z_loss: float = 0.0):
    """Mean token cross-entropy in fp32; labels -100 are ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    loss = (logz - gold) * valid
    if z_loss:
        loss = loss + z_loss * jnp.square(logz) * valid
    denom = jnp.maximum(valid.sum(), 1)
    return loss.sum() / denom
