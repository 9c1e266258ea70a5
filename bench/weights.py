"""Weights of a dense decoder drawn from the seed, on the device, in bf16.

The benchmark, not the program, makes the weights, so that the reference can
make the same ones again without taking anything from the program: both call
:data:`make`, one compiled program, so the bits are the same.  It draws all
layers at once under ``vmap`` (the stacked arrays written directly, no
per-layer copy), on the device that holds the key.

Scales follow the usual initialisation (``1/sqrt(fan_in)``); biases and
norm scales are drawn too, so that a path that drops them shows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def root_key(seed: int):
    """A key from any whole number, all 64 bits of it."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, DTYPE) * scale


def layer(key, s) -> dict:
    """One layer's weights; ``s`` is a :class:`bench.counts.Shapes`."""
    ks = jax.random.split(key, 12)
    d, h, hk, hd, f = s.d, s.heads, s.kv_heads, s.head_dim, s.d_ff
    w = {
        "attn_norm": 1.0 + _normal(ks[0], (d,), 0.1),
        "wq": _normal(ks[1], (d, h, hd), d**-0.5),
        "wk": _normal(ks[2], (d, hk, hd), d**-0.5),
        "wv": _normal(ks[3], (d, hk, hd), d**-0.5),
        "wo": _normal(ks[4], (h, hd, d), (h * hd) ** -0.5),
        "mlp_norm": 1.0 + _normal(ks[5], (d,), 0.1),
        "w_gate": _normal(ks[6], (d, f), d**-0.5),
        "w_up": _normal(ks[7], (d, f), d**-0.5),
        "w_down": _normal(ks[8], (f, d), f**-0.5),
    }
    if s.qkv_bias:
        w["bq"] = _normal(ks[9], (h, hd), 0.1)
        w["bk"] = _normal(ks[10], (hk, hd), 0.1)
        w["bv"] = _normal(ks[11], (hk, hd), 0.1)
    return w


def layer_key(root, i):
    return jax.random.fold_in(jax.random.fold_in(root, 1), i)


def outer(root, s) -> dict:
    """Embedding, final norm and output head."""
    ks = jax.random.split(jax.random.fold_in(root, 2), 3)
    return {
        "embed": _normal(ks[0], (s.vocab, s.d), 1.0),
        "final_norm": 1.0 + _normal(ks[1], (s.d,), 0.1),
        "head": _normal(ks[2], (s.d, s.vocab), s.d**-0.5),
    }


@functools.partial(jax.jit, static_argnums=1)
def make(root, s) -> dict:
    """Every weight: ``outer`` plus ``layers``, each leaf stacked over the
    layers.  ``root`` is :func:`root_key` of the seed, an argument, so that
    one compiled program serves every seed."""
    keys = jax.vmap(lambda i: layer_key(root, i))(jnp.arange(s.layers))
    return {**outer(root, s), "layers": jax.vmap(lambda k: layer(k, s))(keys)}


def to_program(w: dict) -> dict:
    """The same arrays in the parameter tree of ``repro.models``' dense
    decoder (checked against the program's own ``init`` shapes by
    :func:`check_tree`)."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    return {
        "embed": {"table": w["embed"]},
        "layers": {
            "ln_attn": {"scale": lw["attn_norm"]},
            "attn": attn,
            "ln_mlp": {"scale": lw["mlp_norm"]},
            "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")},
        },
        "final_norm": {"scale": w["final_norm"]},
        "head": {"w": w["head"]},
    }


def check_tree(ours, theirs) -> None:
    """Raise unless both trees have the same structure, shapes and dtypes."""
    a = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), ours)
    b = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), theirs)
    if a != b:
        raise ValueError(f"weight tree does not match the program's:\n"
                         f"ours   {a}\nprogram {b}")
