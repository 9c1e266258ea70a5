"""A tiny mixture-of-experts decoder: the program's ``moe`` family at a
reduced size, added to a copy of ``bench/`` as new files alone by
``bench/tests/test_bench_families.py``, which runs it through the harness.

Pre-norm layers of grouped-query attention and a routed expert MLP
(``repro.models.moe``: softmax router, top-k, SwiGLU experts).  Its own
weights, work counts (every weight read once a call, the routed experts'
products counted for each token), one program counter and the work of each
named scope.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    d_expert: int
    vocab: int
    dtype_bytes: int = 2

    @property
    def attn_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2 * self.d * q + 2 * self.d * kv

    @property
    def token_flops(self) -> int:
        """Matrix-product FLOPs of one token through every layer, without
        attention over the cache, and of the head."""
        layer = self.attn_params + self.d * self.experts \
            + self.top_k * 3 * self.d * self.d_expert
        return 2 * self.layers * layer + 2 * self.d * self.vocab

    @property
    def weight_bytes(self) -> int:
        layer = self.attn_params + self.d * self.experts \
            + self.experts * 3 * self.d * self.d_expert + 2 * self.d
        return (self.layers * layer + self.d + self.d * self.vocab) \
            * self.dtype_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.dtype_bytes

    def prefill(self, s: int) -> tuple[float, float]:
        attn = 2 * self.heads * self.head_dim * s * (s + 1) * self.layers
        flops = s * (self.token_flops - 2 * self.d * self.vocab) + attn \
            + 2 * self.d * self.vocab
        nbytes = self.weight_bytes + s * self.d * self.dtype_bytes \
            + s * self.kv_bytes_per_token
        return float(flops), float(nbytes)

    def decode_steps(self, steps: int, lane_positions) -> tuple[float, float]:
        flops, nbytes = 0.0, float(steps * self.weight_bytes)
        for p in lane_positions:
            flops += self.token_flops \
                + 4 * self.heads * self.head_dim * (p + 1) * self.layers
            nbytes += self.d * self.dtype_bytes \
                + (p + 1) * self.kv_bytes_per_token
        return flops, nbytes


def shapes(c: dict) -> Shapes:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Shapes(layers=c["num_hidden_layers"], d=d, heads=h,
                  kv_heads=c["num_key_value_heads"], head_dim=d // h,
                  experts=c["num_local_experts"],
                  top_k=c["num_experts_per_tok"],
                  d_expert=c["moe_intermediate_size"], vocab=c["vocab_size"])


def program_config(c: dict):
    from repro.models.config import ModelConfig, MoEConfig

    return ModelConfig(
        name=Path(c.get("name", "bench")).name, family="moe",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        moe=MoEConfig(num_experts=c["num_local_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_ff_expert=c["moe_intermediate_size"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=False, dtype="bfloat16",
        param_dtype=c["serving"]["param_dtype"])


def _normal(key, shape, scale, dtype=DTYPE):
    return jax.random.normal(key, shape, dtype) * scale


def _layer(key, s: Shapes) -> dict:
    ks = jax.random.split(key, 9)
    d, h, hk, hd = s.d, s.heads, s.kv_heads, s.head_dim
    e, f = s.experts, s.d_expert
    return {
        "ln_attn": {"scale": 1.0 + _normal(ks[0], (d,), 0.1)},
        "attn": {"wq": _normal(ks[1], (d, h, hd), d**-0.5),
                 "wk": _normal(ks[2], (d, hk, hd), d**-0.5),
                 "wv": _normal(ks[3], (d, hk, hd), d**-0.5),
                 "wo": _normal(ks[4], (h, hd, d), (h * hd) ** -0.5)},
        "ln_mlp": {"scale": 1.0 + _normal(ks[5], (d,), 0.1)},
        # the program keeps its router in float32 whatever the weights' type
        "moe": {"router": _normal(ks[6], (d, e), d**-0.5, jnp.float32),
                "w_gate": _normal(ks[7], (e, d, f), d**-0.5),
                "w_up": _normal(ks[8], (e, d, f), d**-0.5),
                "w_down": _normal(jax.random.fold_in(key, 9), (e, f, d),
                                  f**-0.5)},
    }


@functools.partial(jax.jit, static_argnums=1)
def make_weights(root, s: Shapes) -> dict:
    """Every weight, already in the program's parameter tree, the layers
    stacked on a leading axis."""
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.fold_in(root, 1),
                                                 i))(jnp.arange(s.layers))
    ks = jax.random.split(jax.random.fold_in(root, 2), 3)
    return {
        "embed": {"table": _normal(ks[0], (s.vocab, s.d), 1.0)},
        "layers": jax.vmap(lambda k: _layer(k, s))(keys),
        "final_norm": {"scale": 1.0 + _normal(ks[1], (s.d,), 0.1)},
        "head": {"w": _normal(ks[2], (s.d, s.vocab), s.d**-0.5)},
    }


def to_program(w: dict) -> dict:
    return w


def counters(engine) -> dict:
    """Lanes the replica's decode steps ran through the expert layers."""
    return {"moe_lanes": engine.lanes_stepped}


def parts(s: Shapes, kind: str, *args) -> dict:
    """The work of the named scopes of one admission or decode block."""
    if kind == "admit":
        return {"prefill": s.prefill(*args)}
    return {"decode_step": s.decode_steps(*args)}
