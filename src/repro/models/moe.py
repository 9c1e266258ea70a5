"""Mixture-of-Experts layer: top-k routing with grouped, capacity-bounded
dispatch (sort-based, gather-only — the GSPMD-friendly formulation).

Design notes (these choices are what the roofline sees):

* Tokens are split into **groups** (~``tokens_per_group`` each).  Routing,
  sorting and capacity are per-group, so the sort is local to a data shard
  and the dispatched tensor ``xe`` has shape (G, E, C, d) with G sharded
  over the batch axes and E over the model axis (expert parallelism).  The
  group-to-expert resharding is the MoE all-to-all.
* Dispatch/combine are pure **gathers** (argsort + rank arithmetic), never
  scatters — XLA shards gathers well; scatters tend to lower to
  all-gather + select at pod scale.
* Experts compute a SwiGLU at per-expert width; expert weights are read
  once per step (grouped matmul), which is the honest memory cost — the
  Pallas ``grouped_matmul`` kernel mirrors exactly this contraction.
* Capacity overflow drops tokens (contributes zero); the auxiliary
  load-balance loss keeps the router from abusing that.
* ``expert_parallel=False`` (e.g. qwen2-moe's 60 experts on a 16-way model
  axis) shards the expert FFN dim instead — TP-in-expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import MoEConfig


def moe_init(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 6)
    E, f = cfg.num_experts, cfg.d_ff_expert
    s_in = d_model**-0.5
    s_out = f**-0.5
    p = {
        "router": jax.random.normal(ks[0], (d_model, E), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (E, d_model, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (E, d_model, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (E, f, d_model), dtype) * s_out,
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        p["shared"] = {
            "w_gate": jax.random.normal(ks[4], (d_model, fs), dtype) * s_in,
            "w_up": jax.random.normal(ks[5], (d_model, fs), dtype) * s_in,
            "w_down": jax.random.normal(ks[0], (fs, d_model), dtype) * s_out,
            "gate": jnp.zeros((d_model, 1), dtype),
        }
    return p


def expert_specs(sharder, cfg: MoEConfig):
    """PartitionSpec rules for the expert stacks (EP or TP-in-expert)."""
    if cfg.expert_parallel:
        return {
            "router": [None, None],
            "w_gate": ["model", ["fsdp"], None],
            "w_up": ["model", ["fsdp"], None],
            "w_down": ["model", None, ["fsdp"]],
        }
    return {
        "router": [None, None],
        "w_gate": [None, ["fsdp"], "model"],
        "w_up": [None, ["fsdp"], "model"],
        "w_down": [None, "model", ["fsdp"]],
    }


def _group_count(num_tokens: int, tokens_per_group: int) -> int:
    g = max(1, num_tokens // max(tokens_per_group, 1))
    while num_tokens % g:
        g -= 1
    return g


def moe_apply(
    p,
    x,
    cfg: MoEConfig,
    dtype,
    *,
    sharder=None,
    tokens_per_group: int = 4096,
):
    """x: (B, T, d) -> (y, aux_loss)."""
    B, T, d = x.shape
    N = B * T
    E, k = cfg.num_experts, cfg.top_k
    G = _group_count(N, tokens_per_group)
    Tg = N // G
    C = int(np.ceil(Tg * k / E * cfg.capacity_factor))

    if Tg <= 256:
        # decode-sized groups: capacity drops would zero a token's MLP
        # entirely (generation-quality disaster) — go dropless: C = Tg
        # guarantees no expert overflows (each token adds at most 1)
        C = Tg

    xf = x.reshape(G, Tg, d)
    if sharder is not None:
        xf = sharder.constrain(xf, ["batch", None, None])

    # --- routing (fp32) ----------------------------------------------------
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (G,Tg,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                # (G,Tg,k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch/OLMoE form)
    density = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32), axis=(0, 1)
    )
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(density * mean_prob)

    # --- sort pairs by expert within each group -----------------------------
    P_ = Tg * k
    pair_e = top_e.reshape(G, P_)                          # (G,P)
    pair_w = top_w.reshape(G, P_)
    sort = jnp.argsort(pair_e, axis=-1, stable=True)       # (G,P) pair ids ordered by expert
    ranks = jnp.argsort(sort, axis=-1)                     # rank of each pair in that order
    counts = jnp.sum(
        jax.nn.one_hot(pair_e, E, dtype=jnp.int32), axis=1
    )                                                      # (G,E)
    offsets = jnp.cumsum(counts, axis=-1) - counts         # (G,E) exclusive
    pos_in_e = ranks - jnp.take_along_axis(offsets, pair_e, axis=-1)  # (G,P)
    keep = pos_in_e < C

    # --- dispatch: slot (g,e,c) <- token of sorted pair offsets[g,e]+c ------
    slot = offsets[:, :, None] + jnp.arange(C)[None, None, :]          # (G,E,C)
    slot_valid = jnp.arange(C)[None, None, :] < jnp.minimum(counts, C)[:, :, None]
    slot_c = jnp.clip(slot, 0, P_ - 1)
    pair_id = jnp.take_along_axis(sort, slot_c.reshape(G, -1), axis=-1).reshape(G, E, C)
    tok_id = pair_id // k                                   # (G,E,C) token within group
    xe = jnp.take_along_axis(
        xf, tok_id.reshape(G, -1)[..., None], axis=1
    ).reshape(G, E, C, d)
    xe = jnp.where(slot_valid[..., None], xe, 0).astype(dtype)
    if sharder is not None:
        if cfg.expert_parallel:
            xe = sharder.constrain(xe, ["batch", "model", None, None])
        else:
            xe = sharder.constrain(xe, ["batch", None, None, None])

    # --- grouped expert SwiGLU (the grouped_matmul kernel's contraction) ----
    wg, wu, wd = (p["w_gate"].astype(dtype), p["w_up"].astype(dtype),
                  p["w_down"].astype(dtype))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, wg)) * jnp.einsum(
        "gecd,edf->gecf", xe, wu
    )
    if sharder is not None:
        if cfg.expert_parallel:
            h = sharder.constrain(h, ["batch", "model", None, None])
        else:
            h = sharder.constrain(h, ["batch", None, None, "model"])
    ye = jnp.einsum("gecf,efd->gecd", h, wd)                # (G,E,C,d)

    # --- combine: gather each pair's slot, weight, sum over k ---------------
    ye_flat = ye.reshape(G, E * C, d)
    pair_slot = jnp.clip(pair_e * C + pos_in_e, 0, E * C - 1)  # (G,P)
    y_pair = jnp.take_along_axis(ye_flat, pair_slot[..., None], axis=1)  # (G,P,d)
    y_pair = y_pair * (keep * pair_w).astype(dtype)[..., None]
    y = y_pair.reshape(G, Tg, k, d).sum(axis=2)             # (G,Tg,d)
    y = y.reshape(B, T, d)

    # --- shared experts (qwen2-moe) ------------------------------------------
    if "shared" in p:
        ps = p["shared"]
        hs = jax.nn.silu(x @ ps["w_gate"].astype(dtype)) * (x @ ps["w_up"].astype(dtype))
        if sharder is not None:
            hs = sharder.constrain(hs, ["batch", "seq", "model"])
        ys = hs @ ps["w_down"].astype(dtype)
        gate = jax.nn.sigmoid((x @ ps["gate"].astype(dtype)).astype(jnp.float32))
        y = y + ys * gate.astype(dtype)

    if sharder is not None:
        y = sharder.act_btd(y)
    return y, aux
