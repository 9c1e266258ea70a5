"""Plain float32 dense decoder: the reference that decides ``correct``.

Pre-norm decoder layers as the published configurations describe them
(Qwen2, InternLM2): RMSNorm, grouped-query attention with rotary position
embedding in the rotate-half layout and optional q/k/v biases, a SwiGLU
MLP, a final RMSNorm and an untied output head.  Written from that
description in straightforward ``jax.numpy``: no cache, no batching of
requests into slots, no kernels, and nothing imported from the program.
Every matrix product runs at ``Precision.HIGHEST``, so float32 is float32
on a TPU too.

The weights are made again from the seed by :mod:`bench.weights` (the
same compiled program that made the served ones, so the same bits), in bf16
on the chip once the program's state is freed; each layer is widened to
float32 only while it runs.  ``quant="fp8"`` is the control: the same model computed with every
matrix product's inputs rounded to float8 (e4m3, a scale per output
channel for weights and per row for activations), the precision step below
the bf16 that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the reduction axis of the product it feeds)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, quant, a_axis, b_axis):
    if quant == "fp8":
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """Rotate-half RoPE over positions 0..L-1: x (n, L, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, w, c, quant=None):
    """One decoder layer over x (n, L, d), causal within each row."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = rmsnorm(x, w["attn_norm"], eps)
    q = _mm("nld,dhk->nlhk", h, w["wq"], quant, -1, 0)
    k = _mm("nld,dhk->nlhk", h, w["wk"], quant, -1, 0)
    v = _mm("nld,dhk->nlhk", h, w["wv"], quant, -1, 0)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = rope(q, theta), rope(k, theta)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = _mm("nqhk,nshk->nhqs", q, k, quant, -1, -1) / np.sqrt(q.shape[-1])
    L = x.shape[1]
    causal = np.tril(np.ones((L, L), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("nhqs,nshk->nqhk", probs, v, quant, -1, 1)
    x = x + _mm("nqhk,hkd->nqd", o, w["wo"], quant, (-2, -1), (0, 1))
    h = rmsnorm(x, w["mlp_norm"], eps)
    g = _mm("nld,df->nlf", h, w["w_gate"], quant, -1, 0)
    u = _mm("nld,df->nlf", h, w["w_up"], quant, -1, 0)
    return x + _mm("nlf,fd->nld", jax.nn.silu(g) * u, w["w_down"], quant,
                   -1, 0)


def _static(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if k in ("rms_norm_eps", "rope_theta")))


@functools.partial(jax.jit, static_argnames=("c", "quants"))
def _layer_step(xs, layers, i, *, c, quants):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(jnp.float32), layers)
    return tuple(layer(x, w, dict(c), q) for x, q in zip(xs, quants))


@functools.partial(jax.jit, static_argnames=("quants",))
def _embed(tokens, table, *, quants):
    x = table[tokens].astype(jnp.float32)
    return tuple(_fp8(x, -1) if q == "fp8" else x for q in quants)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _logits(h, final_norm, head, *, c, quant):
    """Logits (L, V) of one row of final hidden states h (L, d)."""
    h = rmsnorm(h, final_norm.astype(jnp.float32), dict(c)["rms_norm_eps"])
    return _mm("ld,dv->lv", h, head.astype(jnp.float32), quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("c", "control"))
def _row_gaps(h32, h8, targets, final_norm, head, *, c, control):
    """Per position: how far the reference puts ``targets`` below its best
    token, and (control) how far it puts the fp8 model's first choice."""
    ref = _logits(h32, final_norm, head, c=c, quant=None)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    pick = jnp.argmax(_logits(h8, final_norm, head, c=c, quant="fp8"), -1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def weights(c: dict, seed: int) -> dict:
    """The seed's weights, as :data:`bench.weights.make` makes them."""
    from bench.counts import Shapes

    return W.make(W.root_key(seed), Shapes.from_config(c))


def hidden(c: dict, w: dict, tokens: np.ndarray, quants=(None,)):
    """Final hidden states (before the final norm) of every row of
    ``tokens`` (n, L), once per entry of ``quants``."""
    xs = _embed(jnp.asarray(tokens), w["embed"], quants=tuple(quants))
    for i in range(c["num_hidden_layers"]):
        xs = _layer_step(xs, w["layers"], jnp.int32(i), c=_static(c),
                         quants=tuple(quants))
    return xs


def logits(c: dict, seed: int, tokens: np.ndarray, quant=None):
    """Logits (n, L, V) at every position of every row of ``tokens``."""
    w = weights(c, seed)
    (x,) = hidden(c, w, tokens, (quant,))
    return jnp.stack([_logits(r, w["final_norm"], w["head"], c=_static(c),
                              quant=quant) for r in x])


def gaps(c: dict, seed: int, items, *, rows: int, length: int,
         control: bool = False) -> list[tuple[np.ndarray, np.ndarray]]:
    """For each ``(prompt, served)`` in ``items``: the gap, in the float32
    reference's logits, of every served token below the reference's best
    at its position, and (``control``) the same for the token the fp8
    control puts first there.

    Rows are padded to ``rows`` x ``length`` tokens, so one compiled
    program serves every sample (padding sits after each row's tokens and,
    under causal attention, changes nothing before it).
    """
    if len(items) > rows:
        raise ValueError(f"{len(items)} requests > {rows} reference rows")
    tokens = np.zeros((rows, length), np.int32)
    for r, (prompt, served) in enumerate(items):
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if seq.shape[0] > length:
            raise ValueError(f"sequence of {seq.shape[0]} > {length}")
        tokens[r, : seq.shape[0]] = seq
    w = weights(c, seed)
    xs = hidden(c, w, tokens, (None, "fp8") if control else (None,))
    out = []
    for r, (prompt, served) in enumerate(items):
        n, p = len(served), len(prompt)
        targets = np.zeros(length, np.int32)
        targets[p - 1: p - 1 + n] = served
        g, gc = _row_gaps(xs[0][r], xs[-1][r], jnp.asarray(targets),
                          w["final_norm"], w["head"], c=_static(c),
                          control=control)
        sl = slice(p - 1, p - 1 + n)
        out.append((np.asarray(g)[sl], np.asarray(gc)[sl]))
    return out
