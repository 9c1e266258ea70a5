"""jit'd public wrappers over the Pallas kernels.

The kernels compile for the TPU.  Nothing falls back: a caller without a
TPU (the unit tests, on the CPU) passes ``interpret=True`` itself, which
runs the exact kernel bodies against the ``ref.py`` oracles.  The model code
selects kernels via ``ModelConfig.attn_impl`` — the XLA reference path stays
the default for the dry-run (kernels are opaque custom-calls to
``cost_analysis``, which would blind the roofline).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.grouped_matmul import grouped_matmul as _gmm
from repro.kernels.mamba2_ssd import ssd_chunked_kernel as _ssd
from repro.kernels.mlstm import mlstm_chunked_kernel as _mlstm


def flash_attention_bhsd(q, k, v, *, causal=True, interpret=False):
    """Model-layout wrapper: q (B, S, H, hd); k/v (B, S, Hkv, hd)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    qpk = H // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)
    out = _flash(qf, kf, vf, causal=causal, q_per_kv=qpk, interpret=interpret)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def decode_attention_bhsd(q, k, v, lengths, *, interpret=False):
    """q (B, 1, H, hd); k/v caches (B, S, Hkv, hd); lengths (B,)."""
    B, _, H, hd = q.shape
    Hkv = k.shape[2]
    qpk = H // Hkv
    q4 = q[:, 0].reshape(B, Hkv, qpk, hd)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    out = _decode(q4, kf, vf, lengths, interpret=interpret)
    return out.reshape(B, 1, H, hd)


def mlstm_chunked(q, k, v, i_pre, f_pre, state=None, *, chunk=256,
                  interpret=False):
    """Model layout: q,k (B, S, H, dk); v (B, S, H, dv); gates (B, S, H)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    fl = lambda a, last: a.transpose(0, 2, 1, 3).reshape(B * H, S, last)
    g = lambda a: a.transpose(0, 2, 1).reshape(B * H, S)
    st = None
    if state is not None:
        C, n, m = state
        st = (C.reshape(B * H, *C.shape[2:]), n.reshape(B * H, -1),
              m.reshape(B * H))
    h, (C, n, m) = _mlstm(fl(q, dk), fl(k, dk), fl(v, dv), g(i_pre), g(f_pre),
                          st, chunk=chunk, interpret=interpret)
    h = h.reshape(B, H, S, dv).transpose(0, 2, 1, 3)
    return h, (C.reshape(B, H, dk, dv), n.reshape(B, H, dk), m.reshape(B, H))


def ssd_chunked(x, dt, A, Bm, Cm, D, state=None, *, chunk=256,
                interpret=False):
    """Model layout: x (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,G,N)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    loglam = (A[None, None, :] * dt).transpose(0, 2, 1).reshape(B * H, S)
    Bh = jnp.repeat(Bm, hpg, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    Ch = jnp.repeat(Cm, hpg, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    h0 = None if state is None else state.reshape(B * H, N, P)
    y, hN = _ssd(xf, dtf, loglam, Bh, Ch, h0, chunk=chunk, interpret=interpret)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), hN.reshape(B, H, N, P)


def grouped_matmul(x, w, *, interpret=False, **blocks):
    return _gmm(x, w, interpret=interpret, **blocks)


# re-export the oracles so kernels/<name> + ops + ref travel together
attention_ref = ref.attention_ref
decode_attention_ref = ref.decode_attention_ref
grouped_matmul_ref = ref.grouped_matmul_ref
