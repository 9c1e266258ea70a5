"""The plain float32 reference against the program's own forward pass, at a
reduced size, and the weights it makes again layer by layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.counts import Shapes
from bench.reference import dense_decoder
from bench.tests import tiny

SEED = 2**31 + 3


@pytest.mark.parametrize("bias,kv_heads", [(True, 4), (False, 2)])
def test_reference_matches_the_program_forward(bias, kv_heads):
    from repro.models.api import build_model

    from bench.run import program_config

    c = dict(tiny.CONFIG, qkv_bias=bias, num_key_value_heads=kv_heads)
    cfg = dataclasses.replace(program_config(c), dtype="float32",
                              param_dtype="float32")
    model = build_model(cfg)
    w = weights.to_program(weights.make(weights.root_key(SEED),
                                        Shapes.from_config(c)))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    tokens = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 24),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.forward)(params, {"tokens": tokens}))
    ref = np.asarray(dense_decoder.logits(c, SEED, tokens))
    assert got.shape == ref.shape == (2, 24, c["vocab_size"])
    assert np.max(np.abs(got - ref)) <= 1e-4 * np.max(np.abs(ref))


def test_weights_made_again_are_the_same_bits():
    s = Shapes.from_config(tiny.CONFIG)
    a = weights.make(weights.root_key(SEED), s)
    b = weights.make(weights.root_key(SEED), s)
    c = weights.make(weights.root_key(SEED + 1), s)
    for x, y, z in zip(*map(jax.tree_util.tree_leaves, (a, b, c))):
        assert x.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))


def test_gaps_are_zero_for_the_references_own_choices():
    c = tiny.CONFIG
    prompt = np.arange(5, 21, dtype=np.int32)
    seq = prompt
    served = []
    for _ in range(6):  # greedy under the reference itself
        nxt = int(np.argmax(np.asarray(dense_decoder.logits(c, SEED, seq[None]))[0, -1]))
        served.append(nxt)
        seq = np.append(seq, nxt).astype(np.int32)
    ((g, _),) = dense_decoder.gaps(c, SEED, [(prompt, np.asarray(served))],
                                   rows=2, length=64)
    assert g.shape == (6,) and np.all(g <= 1e-5)
