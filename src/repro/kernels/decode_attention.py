"""Single-token GQA decode attention over a KV cache (Pallas TPU kernel).

Decode attention is **memory-bound**: the entire KV cache streams HBM→VMEM
once per step while compute is a sliver of the MXU.  The kernel therefore:

* processes one (batch, kv-head) pair per grid row with ALL its q_per_kv
  query heads at once (the GQA trick: one KV read amortised over the whole
  query group — q_per_kv × fewer cache bytes than head-by-head);
* streams the cache in (block_k, d) tiles along an *arbitrary* innermost
  grid dim with the online-softmax running state in VMEM scratch;
* masks invalid slots per-sequence from a ``lengths`` vector (continuous
  batching: slots decode at different positions).

Layouts: q (B, Hkv, q_per_kv, d); k/v (B, Hkv, S, d); lengths (B,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bk, scale, nk):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(ik * bk < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (qpk, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (qpk, bk)
        kj = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k=512, interpret=False):
    """q: (B, Hkv, qpk, d); k/v: (B, Hkv, S, d); lengths: (B,) int32.
    Returns (B, Hkv, qpk, d)."""
    B, Hkv, qpk, d = q.shape
    S = k.shape[2]
    bk = min(block_k, S)
    nk = pl.cdiv(S, bk)
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(_decode_kernel, bk=bk, scale=scale, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            # the whole (B,) lengths vector sits in SMEM: a rank-1 block
            # must span the array or a multiple of 128 entries
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, qpk, d), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, d), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qpk, d), jnp.float32),
            pltpu.VMEM((qpk, 1), jnp.float32),
            pltpu.VMEM((qpk, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ham_decode_attention",
    )(lengths.astype(jnp.int32), q, k, v)
