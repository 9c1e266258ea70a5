"""Mamba2 blocks via the State-Space Dual (SSD) chunked algorithm.

Per head: scalar decay λ_t = exp(A·Δ_t) (A < 0), state h ∈ R^{N×P}:

    h_t = λ_t h_{t-1} + Δ_t · (B_t ⊗ x_t)          (B_t ∈ R^N, x_t ∈ R^P)
    y_t = C_t · h_t + D · x_t                       (contract over N)

Chunked (L_t = Σ log λ within chunk):  intra-chunk is a masked matmul
S(t,s) = (C_t·B_s)·exp(L_t−L_s)·Δ_s for s ≤ t (the quadratic "attention-like"
branch the Pallas ``mamba2_ssd`` kernel tiles), inter-chunk is a short scan
carrying h.  B/C are shared across head groups (G groups).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.xlstm import causal_conv, causal_conv_init, causal_conv_step


def ssd_chunked(x, dt, A, Bm, Cm, D, state=None, *, chunk: int):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,G,N); D: (H,).
    Returns (y (B,S,H,P), h_final (B,H,N,P))."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    nc = S // chunk
    assert S % chunk == 0

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    loglam = (A.astype(jnp.float32)[None, None, :] * dtf)  # (B,S,H) negative
    # reshape into chunks: (B,H,nc,L,...)
    def c4(a, last):  # (B,S,H,last) -> (B,H,nc,chunk,last)
        return a.reshape(Bsz, nc, chunk, H, last).transpose(0, 3, 1, 2, 4)

    xc = c4(xf, P)
    dtc = dtf.reshape(Bsz, nc, chunk, H).transpose(0, 3, 1, 2)
    llc = loglam.reshape(Bsz, nc, chunk, H).transpose(0, 3, 1, 2)
    Bc = Bm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N).transpose(0, 3, 1, 2, 4)
    Cc = Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N).transpose(0, 3, 1, 2, 4)

    Lc = jnp.cumsum(llc, axis=-1)  # (B,H,nc,chunk)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    if state is None:
        h0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    else:
        h0 = state.astype(jnp.float32)

    def body(h, xs):
        xi, dti, Li, Bi, Ci = xs      # xi (B,H,L,P), dti/Li (B,H,L), Bi/Ci (B,G,L,N)
        # expand groups to heads
        Bh = jnp.repeat(Bi, hpg, axis=1)   # (B,H,L,N)
        Ch = jnp.repeat(Ci, hpg, axis=1)
        # intra-chunk
        cb = jnp.einsum("bhtn,bhsn->bhts", Ch, Bh)
        decay = jnp.exp(Li[..., :, None] - Li[..., None, :])   # (B,H,t,s)
        Smat = jnp.where(tri, cb * decay * dti[..., None, :], 0.0)
        y = jnp.einsum("bhts,bhsp->bhtp", Smat, xi)
        # inter-chunk
        y = y + jnp.exp(Li)[..., None] * jnp.einsum("bhtn,bhnp->bhtp", Ch, h)
        # state update
        LL = Li[..., -1:]                                      # (B,H,1)
        w = jnp.exp(LL - Li) * dti                             # (B,H,L)
        h_new = jnp.exp(LL)[..., None] * h + jnp.einsum(
            "bhs,bhsn,bhsp->bhnp", w, Bh, xi
        )
        return h_new, y

    xs = (
        xc.transpose(2, 0, 1, 3, 4), dtc.transpose(2, 0, 1, 3),
        Lc.transpose(2, 0, 1, 3), Bc.transpose(2, 0, 1, 3, 4),
        Cc.transpose(2, 0, 1, 3, 4),
    )
    h_fin, ys = jax.lax.scan(body, h0, xs)
    y = ys.transpose(1, 0, 3, 2, 4).reshape(Bsz, S, H, P)
    y = y + xf * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), h_fin


def ssd_step(x, dt, A, Bm, Cm, D, state):
    """One decode step. x: (B,1,H,P); Bm/Cm: (B,1,G,N); state (B,H,N,P)."""
    Bsz, _, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    xf = x[:, 0].astype(jnp.float32)
    dtf = dt[:, 0].astype(jnp.float32)
    lam = jnp.exp(A.astype(jnp.float32)[None, :] * dtf)       # (B,H)
    Bh = jnp.repeat(Bm[:, 0].astype(jnp.float32), hpg, axis=1)  # (B,H,N)
    Ch = jnp.repeat(Cm[:, 0].astype(jnp.float32), hpg, axis=1)
    h = state.astype(jnp.float32)
    h_new = lam[..., None, None] * h + (dtf[..., None, None]
                                        * Bh[..., :, None] * xf[..., None, :])
    y = jnp.einsum("bhn,bhnp->bhp", Ch, h_new) + xf * D.astype(jnp.float32)[None, :, None]
    return y[:, None].astype(x.dtype), h_new


def ssd_recurrent(x, dt, A, Bm, Cm, D, state=None):
    """Oracle: stepwise recurrence (tests compare chunked against this)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[3]
    if state is None:
        state = jnp.zeros((Bsz, H, N, P), jnp.float32)

    def body(h, xs_t):
        xt, dtt, Bt, Ct = xs_t
        y, h = ssd_step(xt[:, None], dtt[:, None], A,
                        Bt[:, None], Ct[:, None], D, h)
        return h, y[:, 0]

    xs = tuple(a.transpose(1, 0, *range(2, a.ndim)) for a in (x, dt, Bm, Cm))
    h, ys = jax.lax.scan(body, state, xs)
    return ys.transpose(1, 0, 2, 3), h


# --------------------------------------------------------------------------
# Mamba2 block
# --------------------------------------------------------------------------


def mamba2_dims(cfg: ModelConfig):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    H = di // s.head_dim
    return di, H, s.num_groups, s.state_dim, s.head_dim


def mamba2_block_init(key, cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    di, H, G, N, P = mamba2_dims(cfg)
    ks = jax.random.split(key, 5)
    dt_ = jnp.dtype(cfg.param_dtype)
    conv_ch = di + 2 * G * N
    return {
        "ln": L.rmsnorm_init(d, dt_),
        # in_proj emits [z, x, B, C, dt]
        "w_in": jax.random.normal(ks[0], (d, 2 * di + 2 * G * N + H), dt_)
        * d**-0.5,
        "conv": causal_conv_init(ks[1], s.conv_width, conv_ch, dt_),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32,
                                       np.log(1e-3), np.log(1e-1)))
        )),
        "D": jnp.ones((H,), jnp.float32),
        "out_norm": L.rmsnorm_init(di, dt_),
        "w_out": jax.random.normal(ks[3], (di, d), dt_) * di**-0.5,
    }


def mamba2_block_apply(p, x, cfg: ModelConfig, *, state=None, sharder=None,
                       decode=False):
    """state = (h (B,H,N,P) fp32, conv_state (B,w-1,conv_ch))."""
    s = cfg.ssm
    dt_ = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    di, H, G, N, P = mamba2_dims(cfg)
    B_, S, _ = x.shape

    hin = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    proj = hin @ p["w_in"].astype(dt_)
    z, xs_, Bm, Cm, dt_pre = jnp.split(
        proj, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1
    )
    if sharder is not None:
        z = sharder.constrain(z, ["batch", None, "model"])
        xs_ = sharder.constrain(xs_, ["batch", None, "model"])
    conv_in = jnp.concatenate([xs_, Bm, Cm], axis=-1)

    if decode:
        h0, conv_state = state
        conv_out, conv_state = causal_conv_step(p["conv"], conv_in, conv_state, dt_)
    else:
        if state is not None:
            h0, conv_state = state
        else:
            h0 = None
        conv_out = causal_conv(p["conv"], conv_in, dt_)
    conv_out = jax.nn.silu(conv_out)
    xc = conv_out[..., :di].reshape(B_, S, H, P)
    Bc = conv_out[..., di : di + G * N].reshape(B_, S, G, N)
    Cc = conv_out[..., di + G * N :].reshape(B_, S, G, N)
    dt_v = jax.nn.softplus(dt_pre.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])

    if decode:
        y, h_new = ssd_step(xc, dt_v, A, Bc, Cc, p["D"], h0)
    else:
        chunk = min(s.chunk_size, S)
        while S % chunk:
            chunk -= 1
        y, h_new = ssd_chunked(xc, dt_v, A, Bc, Cc, p["D"], h0, chunk=chunk)

    yflat = y.reshape(B_, S, di)
    yflat = L.rmsnorm(p["out_norm"], yflat, cfg.norm_eps) * jax.nn.silu(z)
    out = yflat @ p["w_out"].astype(dt_)
    if sharder is not None:
        out = sharder.act_btd(out)
    if decode:
        new_state = (h_new, conv_state)
    else:
        w = s.conv_width
        tail = conv_in[:, -(w - 1):, :]
        pad = jnp.zeros((B_, max(0, w - 1 - S), conv_in.shape[-1]), dt_)
        new_state = (h_new, jnp.concatenate([pad, tail], axis=1))
    return x + out, new_state


def mamba2_state_init(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    di, H, G, N, P = mamba2_dims(cfg)
    conv_ch = di + 2 * G * N
    return (
        jnp.zeros((batch, H, N, P), jnp.float32),
        jnp.zeros((batch, s.conv_width - 1, conv_ch), jnp.dtype(cfg.dtype)),
    )


def mamba2_param_rules(prefix_dims: int = 1):
    """Rules for one (possibly stacked) mamba2 block; ``prefix_dims`` layer
    dims lead each leaf."""
    pre = [None] * prefix_dims
    return {
        "ln": {"scale": pre + [None]},
        "w_in": pre + [["fsdp"], "model"],
        "conv": {"w": pre + [None, "model"]},
        "A_log": pre + [None],
        "dt_bias": pre + [None],
        "D": pre + [None],
        "out_norm": {"scale": pre + [None]},
        "w_out": pre + ["model", ["fsdp"]],
    }
