"""The dense decoder family: pre-norm layers of grouped-query attention
(optional q/k/v biases) and a SwiGLU MLP, as Qwen2 and InternLM2 publish
them.  The harness finds this file by a configuration's ``"family":
"dense"``.  Its work counts are :mod:`bench.counts`, its weights
:mod:`bench.weights`; its reference is ``bench/reference/dense_decoder.py``.
"""

from __future__ import annotations

from pathlib import Path

from bench import counts, weights

#: the weights, drawn on the device from a root key (``weights.root_key``)
make_weights = weights.make
#: those weights in the program's parameter tree
to_program = weights.to_program


def program_config(c: dict):
    """The program's ``ModelConfig`` for the configuration ``c``, with
    every setting the file states."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=Path(c.get("name", "bench")).name, family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], qkv_bias=bool(c["qkv_bias"]),
        mlp="swiglu", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype="bfloat16", param_dtype=c["serving"]["param_dtype"],
    )


def shapes(c: dict) -> counts.Shapes:
    """The work counts: ``prefill(s)`` and ``decode_steps(steps, lanes)``."""
    return counts.Shapes.from_config(c)
