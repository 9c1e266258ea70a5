"""xLSTM: mLSTM (matrix memory, chunkwise-parallel) + sLSTM (scalar memory,
sequential) blocks, arranged mLSTM:sLSTM = 7:1 per group (xLSTM[7:1]).

The mLSTM cell uses exponential gating with the max-stabiliser, computed in
a **chunkwise-parallel** form for train/prefill (matmul-dominated — the
shape the Pallas ``mlstm`` kernel accelerates) and the exact recurrent form
for decode.  Both derive from:

    m_t = max(f̃_t + m_{t-1}, ĩ_t)
    C_t = exp(f̃_t + m_{t-1} - m_t) C_{t-1} + exp(ĩ_t - m_t) k_t v_tᵀ
    n_t = exp(f̃_t + m_{t-1} - m_t) n_{t-1} + exp(ĩ_t - m_t) k_t
    h_t = (q_tᵀ C_t) / max(|q_tᵀ n_t|, exp(-m_t)),   q scaled by 1/√dk

Chunk form (within a chunk, F_t = Σ_{s≤t} f̃_s, a_s = ĩ_s − F_s,
g_t = max(m_prev, cummax_{s≤t} a_s)):  the (t,s) attention-like weight is
exp(a_s − g_t) — F_t cancels — so one chunk is two matmuls plus elementwise
gates, and the inter-chunk state carries (C, n, m).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ModelConfig


# --------------------------------------------------------------------------
# causal conv1d (width-w depthwise), with streaming state for decode
# --------------------------------------------------------------------------


def causal_conv_init(key, width, channels, dtype):
    return {"w": jax.random.normal(key, (width, channels), dtype) * width**-0.5}


def causal_conv(p, x, dtype):
    """x: (B, S, C) -> same shape; causal depthwise conv."""
    w = p["w"].astype(dtype)
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(
        xp[:, i : i + x.shape[1], :] * w[i] for i in range(width)
    )


def causal_conv_step(p, x_t, conv_state, dtype):
    """x_t: (B, 1, C); conv_state: (B, width-1, C) past inputs."""
    w = p["w"].astype(dtype)
    width = w.shape[0]
    window = jnp.concatenate([conv_state, x_t], axis=1)  # (B, width, C)
    out = jnp.einsum("bwc,wc->bc", window, w)[:, None, :]
    return out, window[:, 1:, :]


# --------------------------------------------------------------------------
# mLSTM cell
# --------------------------------------------------------------------------


def mlstm_chunked(q, k, v, i_pre, f_pre, state=None, *, chunk: int):
    """Chunkwise-parallel mLSTM.

    q,k,v: (B, S, H, dk|dv); i_pre/f_pre: (B, S, H) raw gate pre-activations.
    state: optional (C (B,H,dk,dv), n (B,H,dk), m (B,H)).
    Returns (h (B,S,H,dv), final_state).
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    q = q / np.sqrt(dk)
    nc = S // chunk
    assert S % chunk == 0, "sequence must be divisible by chunk"
    # (B, H, nc, L, ...)
    qc = q.reshape(B, nc, chunk, H, dk).transpose(0, 3, 1, 2, 4)
    kc = k.reshape(B, nc, chunk, H, dk).transpose(0, 3, 1, 2, 4)
    vc = v.reshape(B, nc, chunk, H, dv).transpose(0, 3, 1, 2, 4)
    ic = i_pre.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2).astype(jnp.float32)
    fc = jax.nn.log_sigmoid(
        f_pre.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2).astype(jnp.float32)
    )

    F = jnp.cumsum(fc, axis=-1)                      # (B,H,nc,L)
    a = ic - F                                        # log source weights
    a_cmax = jax.lax.cummax(a, axis=a.ndim - 1)

    if state is None:
        C0 = jnp.zeros((B, H, dk, dv), jnp.float32)
        n0 = jnp.zeros((B, H, dk), jnp.float32)
        m0 = jnp.full((B, H), -jnp.inf, jnp.float32)
    else:
        C0, n0, m0 = (s.astype(jnp.float32) for s in state)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunk_body(carry, xs):
        C, n, m = carry
        qi, ki, vi, Fi, ai, acm = xs  # (B,H,L,*) for this chunk
        g = jnp.maximum(m[..., None], acm)            # (B,H,L)
        # intra-chunk
        w_ts = jnp.exp(ai[..., None, :] - g[..., :, None])  # (B,H,L,L): exp(a_s - g_t)
        scores = jnp.einsum("bhtk,bhsk->bhts", qi.astype(jnp.float32), ki.astype(jnp.float32))
        Smat = jnp.where(tri, scores * w_ts, 0.0)
        num = jnp.einsum("bhts,bhsv->bhtv", Smat, vi.astype(jnp.float32))
        den = Smat.sum(-1)
        # inter-chunk
        scale = jnp.exp(m[..., None] - g)             # (B,H,L)
        qC = jnp.einsum("bhtk,bhkv->bhtv", qi.astype(jnp.float32), C)
        qn = jnp.einsum("bhtk,bhk->bht", qi.astype(jnp.float32), n)
        num = num + scale[..., None] * qC
        den = den + scale * qn
        m_t = Fi + g
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # state update (end of chunk)
        gL = g[..., -1]
        FL = Fi[..., -1]
        decay_src = jnp.exp(ai - gL[..., None])       # (B,H,L)
        C_new = jnp.exp(m - gL)[..., None, None] * C + jnp.einsum(
            "bhs,bhsk,bhsv->bhkv", decay_src, ki.astype(jnp.float32), vi.astype(jnp.float32)
        )
        n_new = jnp.exp(m - gL)[..., None] * n + jnp.einsum(
            "bhs,bhsk->bhk", decay_src, ki.astype(jnp.float32)
        )
        m_new = FL + gL
        return (C_new, n_new, m_new), h

    xs = (
        qc.transpose(2, 0, 1, 3, 4), kc.transpose(2, 0, 1, 3, 4),
        vc.transpose(2, 0, 1, 3, 4), F.transpose(2, 0, 1, 3),
        a.transpose(2, 0, 1, 3), a_cmax.transpose(2, 0, 1, 3),
    )
    (C, n, m), hs = jax.lax.scan(chunk_body, (C0, n0, m0), xs)
    # hs: (nc, B, H, L, dv) -> (B, S, H, dv)
    h = hs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, dv)
    return h.astype(v.dtype), (C, n, m)


def mlstm_step(q, k, v, i_pre, f_pre, state):
    """Exact recurrent step.  q,k,v: (B,1,H,d*); gates (B,1,H)."""
    B, _, H, dk = q.shape
    out_dtype = v.dtype
    q = (q[:, 0] / np.sqrt(dk)).astype(jnp.float32)
    k = k[:, 0].astype(jnp.float32)
    v = v[:, 0].astype(jnp.float32)
    i_t = i_pre[:, 0].astype(jnp.float32)
    f_t = jax.nn.log_sigmoid(f_pre[:, 0].astype(jnp.float32))
    C, n, m = (s.astype(jnp.float32) for s in state)
    m_new = jnp.maximum(f_t + m, i_t)
    fp = jnp.exp(f_t + m - m_new)
    ip = jnp.exp(i_t - m_new)
    C_new = fp[..., None, None] * C + ip[..., None, None] * (
        k[..., :, None] * v[..., None, :]
    )
    n_new = fp[..., None] * n + ip[..., None] * k
    num = jnp.einsum("bhk,bhkv->bhv", q, C_new)
    den = jnp.einsum("bhk,bhk->bh", q, n_new)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h[:, None].astype(out_dtype), (C_new, n_new, m_new)


def mlstm_recurrent(q, k, v, i_pre, f_pre, state=None):
    """Oracle: full recurrence via scan over time (tests compare chunked
    against this)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = (
            jnp.zeros((B, H, dk, dv), jnp.float32),
            jnp.zeros((B, H, dk), jnp.float32),
            jnp.full((B, H), -jnp.inf, jnp.float32),
        )

    def body(st, xs):
        qt, kt, vt, it, ft = xs
        h, st = mlstm_step(qt[:, None], kt[:, None], vt[:, None],
                           it[:, None], ft[:, None], st)
        return st, h[:, 0]

    xs = tuple(arr.transpose(1, 0, *range(2, arr.ndim))
               for arr in (q, k, v, i_pre, f_pre))
    state, hs = jax.lax.scan(body, state, xs)
    return hs.transpose(1, 0, 2, 3), state


# --------------------------------------------------------------------------
# mLSTM block
# --------------------------------------------------------------------------


def mlstm_block_init(key, cfg: ModelConfig):
    x = cfg.xlstm
    d = cfg.d_model
    di = int(x.proj_factor * d)
    dqk = int(x.qk_factor * di)
    H = cfg.num_heads
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    s = d**-0.5
    si = di**-0.5
    return {
        "ln": L.rmsnorm_init(d, dt),
        "w_up": jax.random.normal(ks[0], (d, 2 * di), dt) * s,
        "conv": causal_conv_init(ks[1], x.conv_width, di, dt),
        "wq": jax.random.normal(ks[2], (di, dqk), dt) * si,
        "wk": jax.random.normal(ks[3], (di, dqk), dt) * si,
        "wv": jax.random.normal(ks[4], (di, di), dt) * si,
        "w_if": jax.random.normal(ks[5], (di, 2 * H), dt) * si,
        "b_if": jnp.concatenate([jnp.zeros((H,), dt),
                                 jnp.linspace(3.0, 6.0, H).astype(dt)]),
        "out_norm": L.rmsnorm_init(di, dt),
        "w_down": jax.random.normal(ks[6], (di, d), dt) * si,
    }


def mlstm_block_apply(p, x, cfg: ModelConfig, *, state=None, sharder=None,
                      decode=False):
    """Returns (y, new_state); state = (C, n, m, conv_state)."""
    xl = cfg.xlstm
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    di = int(xl.proj_factor * d)
    dqk = int(xl.qk_factor * di)
    H = cfg.num_heads
    dh = di // H
    dk = dqk // H
    B, S, _ = x.shape

    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    up = h @ p["w_up"].astype(dt)
    xi, z = jnp.split(up, 2, axis=-1)
    if sharder is not None:
        xi = sharder.constrain(xi, ["batch", None, "model"])
        z = sharder.constrain(z, ["batch", None, "model"])

    if decode:
        C, n, m, conv_state = state
        xc, conv_state = causal_conv_step(p["conv"], xi, conv_state, dt)
    else:
        conv_state = None
        if state is not None:
            C, n, m, conv_state = state
        else:
            C = n = m = None
        xc = causal_conv(p["conv"], xi, dt)
    xc = jax.nn.silu(xc)

    q = (xc @ p["wq"].astype(dt)).reshape(B, S, H, dk)
    k = (xc @ p["wk"].astype(dt)).reshape(B, S, H, dk)
    v = (xi @ p["wv"].astype(dt)).reshape(B, S, H, dh)
    gates = xc @ p["w_if"].astype(dt) + p["b_if"].astype(dt)
    i_pre, f_pre = jnp.split(gates.reshape(B, S, 2 * H), 2, axis=-1)

    if decode:
        hcell, (C, n, m) = mlstm_step(q, k, v, i_pre, f_pre, (C, n, m))
    else:
        cell_state = None if C is None else (C, n, m)
        chunk = min(xl.chunk_size, S)
        while S % chunk:
            chunk -= 1
        hcell, (C, n, m) = mlstm_chunked(
            q, k, v, i_pre, f_pre, cell_state, chunk=chunk
        )

    hflat = hcell.reshape(B, S, di)
    hflat = L.rmsnorm(p["out_norm"], hflat, cfg.norm_eps)
    y = (hflat * jax.nn.silu(z)) @ p["w_down"].astype(dt)
    if sharder is not None:
        y = sharder.act_btd(y)
    if decode:
        new_state = (C, n, m, conv_state)
    else:
        width = xl.conv_width
        tail = xi[:, -(width - 1):, :]
        pad = jnp.zeros((B, max(0, width - 1 - S), di), dt)
        new_state = (C, n, m, jnp.concatenate([pad, tail], axis=1))
    return x + y, new_state


def mlstm_state_init(cfg: ModelConfig, batch: int):
    xl = cfg.xlstm
    d = cfg.d_model
    di = int(xl.proj_factor * d)
    H = cfg.num_heads
    dh = di // H
    dk = int(xl.qk_factor * di) // H
    return (
        jnp.zeros((batch, H, dk, dh), jnp.float32),
        jnp.zeros((batch, H, dk), jnp.float32),
        jnp.full((batch, H), -1e30, jnp.float32),
        jnp.zeros((batch, xl.conv_width - 1, di), jnp.dtype(cfg.dtype)),
    )


# --------------------------------------------------------------------------
# sLSTM block (sequential scan; block-diagonal per-head recurrence)
# --------------------------------------------------------------------------


def slstm_block_init(key, cfg: ModelConfig):
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    s = d**-0.5
    ffs = int(4 * d / 3)
    return {
        "ln": L.rmsnorm_init(d, dt),
        "conv": causal_conv_init(ks[0], cfg.xlstm.conv_width, d, dt),
        "w_gates": jax.random.normal(ks[1], (d, 4 * d), dt) * s,   # i,f,z,o
        "r_gates": jax.random.normal(ks[2], (4, H, dh, dh), dt) * dh**-0.5,
        "b_gates": jnp.concatenate([
            jnp.zeros((d,), dt),
            jnp.full((d,), 3.0, dt),            # forget bias
            jnp.zeros((2 * d,), dt),
        ]),
        "out_norm": L.rmsnorm_init(d, dt),
        "w_up": jax.random.normal(ks[3], (d, 2 * ffs), dt) * s,     # GeGLU
        "w_down": jax.random.normal(ks[4], (ffs, d), dt) * ffs**-0.5,
    }


def _slstm_cell(gates_x, hcnm, r_gates):
    """One timestep.  gates_x: (B, 4d) input contribution; state
    (h, c, n, m): each (B, d) [m in fp32]."""
    h, c, n, m = hcnm
    B, d4 = gates_x.shape
    d = d4 // 4
    H, dh = r_gates.shape[1], r_gates.shape[2]
    hh = h.reshape(B, H, dh)
    rec = jnp.einsum("bhk,ghkl->bghl", hh.astype(r_gates.dtype), r_gates)
    rec = rec.reshape(B, 4 * d)
    pre = (gates_x + rec).astype(jnp.float32)
    i_p, f_p, z_p, o_p = jnp.split(pre, 4, axis=-1)
    f_log = jax.nn.log_sigmoid(f_p)
    m_new = jnp.maximum(f_log + m, i_p)
    i_g = jnp.exp(i_p - m_new)
    f_g = jnp.exp(f_log + m - m_new)
    c_new = f_g * c + i_g * jnp.tanh(z_p)
    n_new = f_g * n + i_g
    h_new = jax.nn.sigmoid(o_p) * c_new / jnp.maximum(n_new, 1e-6)
    return (h_new.astype(h.dtype), c_new, n_new, m_new)


def slstm_block_apply(p, x, cfg: ModelConfig, *, state=None, sharder=None,
                      decode=False):
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    B, S, _ = x.shape
    hin = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    if decode:
        (h0, c0, n0, m0, conv_state) = state
        xc, conv_state = causal_conv_step(p["conv"], hin, conv_state, dt)
    else:
        if state is None:
            h0 = jnp.zeros((B, d), dt)
            c0 = jnp.zeros((B, d), jnp.float32)
            n0 = jnp.zeros((B, d), jnp.float32)
            m0 = jnp.full((B, d), -1e30, jnp.float32)
        else:
            h0, c0, n0, m0, _ = state
        xc = causal_conv(p["conv"], hin, dt)
    xc = jax.nn.silu(xc)
    gates_x = xc @ p["w_gates"].astype(dt) + p["b_gates"].astype(dt)

    if decode:
        st = _slstm_cell(gates_x[:, 0], (h0, c0, n0, m0), p["r_gates"])
        hs = st[0][:, None]
        h0, c0, n0, m0 = st
    else:
        def body(carry, g_t):
            st = _slstm_cell(g_t, carry, p["r_gates"])
            return st, st[0]

        (h0, c0, n0, m0), hs = jax.lax.scan(
            body, (h0, c0, n0, m0), gates_x.transpose(1, 0, 2)
        )
        hs = hs.transpose(1, 0, 2)

    hs = L.rmsnorm(p["out_norm"], hs, cfg.norm_eps)
    up = hs @ p["w_up"].astype(dt)
    a, b = jnp.split(up, 2, axis=-1)
    y = (jax.nn.gelu(a) * b) @ p["w_down"].astype(dt)
    if sharder is not None:
        y = sharder.act_btd(y)
    if decode:
        new_state = (h0, c0, n0, m0, conv_state)
    else:
        width = cfg.xlstm.conv_width
        tail = hin[:, -(width - 1):, :]
        pad = jnp.zeros((B, max(0, width - 1 - S), d), dt)
        new_state = (h0, c0, n0, m0, jnp.concatenate([pad, tail], axis=1))
    return x + y, new_state


def slstm_state_init(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    return (
        jnp.zeros((batch, d), dt),
        jnp.zeros((batch, d), jnp.float32),
        jnp.zeros((batch, d), jnp.float32),
        jnp.full((batch, d), -1e30, jnp.float32),
        jnp.zeros((batch, cfg.xlstm.conv_width - 1, d), dt),
    )


# --------------------------------------------------------------------------
# full xLSTM model: groups of (mlstm_per_group mLSTM + slstm_per_group sLSTM)
# --------------------------------------------------------------------------


def _group_counts(cfg: ModelConfig):
    xl = cfg.xlstm
    per = xl.mlstm_per_group + xl.slstm_per_group
    assert cfg.num_layers % per == 0, "num_layers must divide the group size"
    return cfg.num_layers // per, xl.mlstm_per_group, xl.slstm_per_group


def xlstm_init(key, cfg: ModelConfig):
    G, M, Sl = _group_counts(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, G * (M + Sl) + 2)
    ki = iter(keys)
    m_blocks = [[mlstm_block_init(next(ki), cfg) for _ in range(M)] for _ in range(G)]
    s_blocks = [[slstm_block_init(next(ki), cfg) for _ in range(Sl)] for _ in range(G)]
    stack = lambda blocks: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": L.embedding_init(next(ki), cfg.vocab_size, cfg.d_model, dt),
        "mlstm": stack([stack(g) for g in m_blocks]),   # leaves (G, M, ...)
        "slstm": stack([stack(g) for g in s_blocks]),   # leaves (G, Sl, ...)
        "final_norm": L.rmsnorm_init(cfg.d_model, dt),
        "head": {"w": jax.random.normal(keys[-1], (cfg.d_model, cfg.vocab_size), dt)
                 * (1.0 / cfg.d_model**0.5)},
    }


def xlstm_forward(p, batch, cfg: ModelConfig, *, sharder=None,
                  return_cache=False):
    dt = jnp.dtype(cfg.dtype)
    x = L.embed(p["embed"], batch["tokens"], dt)
    if sharder is not None:
        x = sharder.act_btd(x)
    B = x.shape[0]

    def m_body(x, layer_p):
        x, st = mlstm_block_apply(layer_p, x, cfg, sharder=sharder)
        return x, st if return_cache else None

    def s_body(x, layer_p):
        x, st = slstm_block_apply(layer_p, x, cfg, sharder=sharder)
        return x, st if return_cache else None

    def group_body(x, group_p):
        mp, sp = group_p
        x, mst = jax.lax.scan(jax.checkpoint(m_body) if cfg.remat != "none" else m_body, x, mp)
        x, sst = jax.lax.scan(jax.checkpoint(s_body) if cfg.remat != "none" else s_body, x, sp)
        return x, (mst, sst)

    x, states = jax.lax.scan(group_body, x, (p["mlstm"], p["slstm"]))
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(p["head"], x, dt)
    if sharder is not None:
        logits = sharder.logits(logits)
    return logits, (states if return_cache else None), jnp.zeros((), jnp.float32)


def xlstm_init_cache(cfg: ModelConfig, batch: int, max_len: int, **_):
    G, M, Sl = _group_counts(cfg)
    rep = lambda st, k: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (G, k) + a.shape).copy(), st
    )
    return {
        "mlstm": rep(mlstm_state_init(cfg, batch), M),
        "slstm": rep(slstm_state_init(cfg, batch), Sl),
    }


def xlstm_decode_step(p, cache, batch, cfg: ModelConfig, *, sharder=None):
    dt = jnp.dtype(cfg.dtype)
    x = L.embed(p["embed"], batch["tokens"], dt)

    def m_body(x, layer_in):
        layer_p, st = layer_in
        x, st = mlstm_block_apply(layer_p, x, cfg, state=st, decode=True,
                                  sharder=sharder)
        return x, st

    def s_body(x, layer_in):
        layer_p, st = layer_in
        x, st = slstm_block_apply(layer_p, x, cfg, state=st, decode=True,
                                  sharder=sharder)
        return x, st

    def group_body(x, group_in):
        mp, mst, sp, sst = group_in
        x, mst = jax.lax.scan(m_body, x, (mp, mst))
        x, sst = jax.lax.scan(s_body, x, (sp, sst))
        return x, (mst, sst)

    x, (mst, sst) = jax.lax.scan(
        group_body, x, (p["mlstm"], cache["mlstm"], p["slstm"], cache["slstm"])
    )
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(p["head"], x, dt)
    if sharder is not None:
        logits = sharder.logits(logits)
    return logits, {"mlstm": mst, "slstm": sst}


def xlstm_param_rules(cfg: ModelConfig):
    mb = {
        "ln": {"scale": [None, None, None]},
        "w_up": [None, None, ["fsdp"], "model"],
        "conv": {"w": [None, None, None, "model"]},
        "wq": [None, None, "model", None],
        "wk": [None, None, "model", None],
        "wv": [None, None, "model", None],
        "w_if": [None, None, "model", None],
        "b_if": [None, None, None],
        "out_norm": {"scale": [None, None, None]},
        "w_down": [None, None, "model", ["fsdp"]],
    }
    sb = {
        "ln": {"scale": [None, None, None]},
        "conv": {"w": [None, None, None, None]},
        "w_gates": [None, None, ["fsdp"], None],
        "r_gates": [None, None, None, None, None, None],
        "b_gates": [None, None, None],
        "out_norm": {"scale": [None, None, None]},
        "w_up": [None, None, ["fsdp"], "model"],
        "w_down": [None, None, "model", ["fsdp"]],
    }
    return {
        "embed": {"table": [["fsdp"], "model"]},
        "mlstm": mb,
        "slstm": sb,
        "final_norm": {"scale": [None]},
        "head": {"w": [["fsdp"], "model"]},
    }
