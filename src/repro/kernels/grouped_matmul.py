"""Grouped (per-expert) matmul Pallas TPU kernel for the MoE layer.

Computes out[e] = x[e] @ w[e] for every expert e over the capacity-padded
dispatch layout (E, C, d) × (E, d, f) → (E, C, f) — the exact contraction
``moe_apply`` issues twice per layer (up/gate) plus once transposed (down).

MXU-aligned tiling: (bc × bd) · (bd × bf) accumulated in fp32 VMEM scratch
over the inner-d grid dim (sequential), output written on the last d-step.
Expert weights stream tile-by-tile — each expert's weights are read once
per step regardless of how many tokens routed to it, which is the memory
behaviour that makes the capacity layout the right one for decode too
(see DESIGN.md §Roofline discussion of MoE).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd):
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32)
    )

    @pl.when(kd == nd - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret")
)
def grouped_matmul(x, w, *, block_c=128, block_f=128, block_d=512,
                   interpret=False):
    """x: (E, C, d); w: (E, d, f) -> (E, C, f)."""
    E, C, d = x.shape
    f = w.shape[-1]
    bc, bf, bd = min(block_c, C), min(block_f, f), min(block_d, d)
    nc, nf, nd = pl.cdiv(C, bc), pl.cdiv(f, bf), pl.cdiv(d, bd)

    kernel = functools.partial(_gmm_kernel, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, ic, jf, kd: (e, ic, kd)),
            pl.BlockSpec((1, bd, bf), lambda e, ic, jf, kd: (e, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, ic, jf, kd: (e, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="ham_grouped_matmul",
    )(x, w)
