"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Same TPU shape as the mLSTM kernel: grid = (batch·head, chunks) with the
chunk axis sequential and the SSM state h ∈ R^{N×P} carried in VMEM
scratch.  The within-chunk cumulative log-decay is a lower-triangular
matmul; the quadratic intra-chunk branch is two MXU matmuls
((C·Bᵀ)-tile and the (L,L)×(L,P) apply); the inter-chunk branch is a
(L,N)×(N,P) matmul against the carried state.

Inputs (pre-chunked, B/C pre-expanded to heads):
    x (BH, nc, L, P); dt, loglam (BH, nc, L); Bm, Cm (BH, nc, L, N);
    h0 (BH, N, P).
Outputs: y (BH, nc, L, P) and the final state h (BH, N, P).
The D·x skip connection is applied by the ops wrapper (elementwise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, ll_ref, b_ref, c_ref, h0_ref, y_ref, hN_ref,
                h_ref, *, L, nc):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)       # (L, P)
    dt = dt_ref[0, ic].astype(jnp.float32)    # (L,)
    ll = ll_ref[0, ic].astype(jnp.float32)    # (L,) log lambda (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)      # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)      # (L, N)

    tril = jnp.tril(jnp.ones((L, L), jnp.float32))
    Lc = jnp.dot(tril, ll[:, None])[:, 0]     # inclusive cumsum (L,)

    # intra-chunk: S(t,s) = (C_t·B_s) exp(Lc_t - Lc_s) dt_s, s <= t
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (L, L)
    decay = jnp.exp(Lc[:, None] - Lc[None, :])
    s_mat = jnp.where(tril > 0, cb * decay * dt[None, :], 0.0)
    y = jnp.dot(s_mat, x)

    # inter-chunk: exp(Lc_t) C_t · h_prev
    y = y + jnp.exp(Lc)[:, None] * jnp.dot(Cm, h_ref[...])
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(LL) h + Σ_s exp(LL - Lc_s) dt_s B_s ⊗ x_s
    LL = Lc[L - 1]
    w = jnp.exp(LL - Lc) * dt                 # (L,)
    h_ref[...] = jnp.exp(LL) * h_ref[...] + jax.lax.dot_general(
        Bm * w[:, None], x, (((0,), (0,)), ((), ()))
    )

    @pl.when(ic == nc - 1)
    def _emit():
        hN_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_kernel(x, dt, loglam, Bm, Cm, h0=None, *, chunk=256,
                       interpret=False):
    """x: (BH, S, P); dt/loglam: (BH, S); Bm/Cm: (BH, S, N);
    h0: (BH, N, P).  Returns (y (BH, S, P), h (BH, N, P))."""
    BH, S, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    if h0 is None:
        h0 = jnp.zeros((BH, N, P), jnp.float32)

    rc = lambda a, last: a.reshape(BH, nc, L, last)
    kernel = functools.partial(_ssd_kernel, L=L, nc=nc)
    y, hN = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bh, ic: (bh, ic, 0, 0)),
            # gates: every chunk of a row at once — a (1, L) tile of the
            # (nc, L) gate matrix would break the TPU's (8, 128) tiling
            pl.BlockSpec((1, nc, L), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, nc, L), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bh, ic: (bh, ic, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bh, ic: (bh, ic, 0, 0)),
            pl.BlockSpec((1, N, P), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bh, ic: (bh, ic, 0, 0)),
            pl.BlockSpec((1, N, P), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, L, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ham_mamba2_ssd",
    )(rc(x, P), dt.reshape(BH, nc, L), loglam.reshape(BH, nc, L),
      rc(Bm, N), rc(Cm, N), h0)
    return y.reshape(BH, S, P), hN
