"""Chunkwise-parallel mLSTM Pallas TPU kernel (TFLA-style).

One grid row = one (batch, head); the chunk axis is the innermost grid dim
with *arbitrary* (sequential) semantics, carrying the matrix memory
(C ∈ R^{dk×dv}), normaliser (n ∈ R^{dk}) and max-stabiliser (m) in VMEM
scratch across chunks — the TPU-shaped replacement for the GPU kernel's
inter-block state passing through HBM.

Everything inside a chunk is matmuls and elementwise VPU work:
* the within-chunk cumulative log-forget F = tril·f̃ is computed as a
  lower-triangular MATMUL (MXU) instead of a sequential cumsum;
* the running max g_t = max(m_prev, cummax a) is a masked row-max over the
  (L, L) tile — no scan primitives, Mosaic-friendly;
* the (t,s) decay weights multiply the (q·kᵀ) score tile elementwise.

Inputs (pre-chunked): q, k (BH, nc, L, dk); v (BH, nc, L, dv);
i_pre, f_pre (BH, nc, L); initial state C0 (BH, dk, dv), n0 (BH, dk),
m0 (BH, 1).  Outputs: h (BH, nc, L, dv) and the final (C, n, m).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, c0_ref, n0_ref, m0_ref,
                  h_ref, cN_ref, nN_ref, mN_ref, C_ref, n_ref, m_ref, *,
                  L, scale, nc):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        C_ref[...] = c0_ref[0].astype(jnp.float32)
        n_ref[...] = n0_ref[0].astype(jnp.float32)     # (1, dk)
        m_ref[...] = m0_ref[0].astype(jnp.float32)     # (1, 1)

    q = q_ref[0, 0].astype(jnp.float32) * scale         # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)                 # (L, dv)
    i_pre = i_ref[0, ic].astype(jnp.float32)            # (L,)
    f_log = jax.nn.log_sigmoid(f_ref[0, ic].astype(jnp.float32))

    tril = jnp.tril(jnp.ones((L, L), jnp.float32))      # includes diagonal
    F = jnp.dot(tril, f_log[:, None])[:, 0]             # inclusive cumsum (L,)
    a = i_pre - F                                       # (L,)

    m_prev = m_ref[0, 0]
    # running max: g_t = max(m_prev, max_{s<=t} a_s) via masked row-max
    big_neg = jnp.float32(-1e30)
    a_mat = jnp.where(tril > 0, a[None, :], big_neg)    # (t, s)
    g = jnp.maximum(m_prev, jnp.max(a_mat, axis=1))     # (L,)

    # intra-chunk decay-weighted scores
    w_ts = jnp.exp(jnp.where(tril > 0, a[None, :] - g[:, None], big_neg))
    s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * w_ts

    # inter-chunk contribution
    scale_t = jnp.exp(m_prev - g)                       # (L,)
    num = jnp.dot(s_mat, v) + scale_t[:, None] * jnp.dot(q, C_ref[...])
    den = jnp.sum(s_mat, axis=1) + scale_t * jnp.dot(q, n_ref[0])
    m_t = F + g
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[:, None]
    h_ref[0, 0] = h.astype(h_ref.dtype)

    # state update
    gL = g[L - 1]
    FL = F[L - 1]
    decay_src = jnp.exp(a - gL)                         # (L,)
    C_ref[...] = jnp.exp(m_prev - gL) * C_ref[...] + jax.lax.dot_general(
        k * decay_src[:, None], v, (((0,), (0,)), ((), ()))
    )
    n_ref[...] = jnp.exp(m_prev - gL) * n_ref[...] + jnp.dot(
        decay_src[None, :], k
    )
    m_ref[...] = jnp.full_like(m_ref, FL + gL)

    @pl.when(ic == nc - 1)
    def _emit_state():
        cN_ref[0] = C_ref[...]
        nN_ref[0] = n_ref[...]
        mN_ref[0] = m_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunked_kernel(q, k, v, i_pre, f_pre, state=None, *, chunk=256,
                         interpret=False):
    """q,k: (BH, S, dk); v: (BH, S, dv); gates: (BH, S).
    Returns (h (BH, S, dv), (C, n, m))."""
    BH, S, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L

    rc = lambda a, last: a.reshape(BH, nc, L, last)
    qs, ks_, vs = rc(q, dk), rc(k, dk), rc(v, dv)
    is_, fs = i_pre.reshape(BH, nc, L), f_pre.reshape(BH, nc, L)
    if state is None:
        C0 = jnp.zeros((BH, dk, dv), jnp.float32)
        n0 = jnp.zeros((BH, dk), jnp.float32)
        m0 = jnp.full((BH, 1), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state
    # per-row state as (1, x) tiles: a (1, x) block of a (BH, x) array
    # would break the TPU's (8, 128) tiling
    n0 = n0.reshape(BH, 1, dk)
    m0 = m0.reshape(BH, 1, 1)

    kernel = functools.partial(_mlstm_kernel, L=L, scale=1.0 / np.sqrt(dk),
                               nc=nc)
    h, cN, nN, mN = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, dk), lambda bh, ic: (bh, ic, 0, 0)),
            pl.BlockSpec((1, 1, L, dk), lambda bh, ic: (bh, ic, 0, 0)),
            pl.BlockSpec((1, 1, L, dv), lambda bh, ic: (bh, ic, 0, 0)),
            # gates: every chunk of a row at once — a (1, L) tile of the
            # (nc, L) gate matrix would break the TPU's (8, 128) tiling
            pl.BlockSpec((1, nc, L), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, nc, L), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, dv), lambda bh, ic: (bh, ic, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, L, dv), v.dtype),
            jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, dk), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ham_mlstm_chunked",
    )(qs, ks_, vs, is_, fs, C0, n0, m0)
    return h.reshape(BH, S, dv), (cN, nN.reshape(BH, dk), mN.reshape(BH))
