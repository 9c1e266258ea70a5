"""Flash attention (tiled online-softmax) Pallas TPU kernel, GQA-aware.

TPU adaptation notes (DESIGN.md §2: adapt, don't port):
* Tiling is chosen for VMEM + MXU: q/k tiles are multiples of 128 on the
  matmul dims; the (bq, bk) score tile stays in VMEM/VREGs.
* The kv-block axis is the innermost grid dim with *arbitrary* semantics —
  TPU grids execute it sequentially per core, so the online-softmax running
  state (m, l, acc) lives in VMEM scratch across grid steps (no atomics, no
  shared-memory reductions — the GPU mechanics that do NOT transfer).
* GQA: the kv head index is derived in the index_map (h // q_per_kv), so
  repeated KV heads are never materialised.
* Causal masking skips whole tiles above the diagonal via ``pl.when``.

Layouts: q (BH, S, d), k/v (BKV, S, d) with BH = B*H, BKV = B*Hkv.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq, bk, causal, scale, nk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        # tile fully above the diagonal -> skip
        run = (ik * bk) <= (iq * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kj = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kj <= qi, s, NEG_INF)
        m_prev = m_ref[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # (bk, d)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_per_kv", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal=True, q_per_kv=1, block_q=256, block_k=512,
    interpret=False,
):
    """q: (BH, S, d); k, v: (BKV, S, d) with BH = BKV * q_per_kv
    (head-major: q head g*q_per_kv+j reads kv head g).  Returns (BH, S, d).
    """
    BH, S, d = q.shape
    bq = min(block_q, S)
    bk = min(block_k, k.shape[1])
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(k.shape[1], bk)
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, scale=scale, nk=nk
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh // q_per_kv, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh // q_per_kv, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ham_flash_attention",
    )(q, k, v)
