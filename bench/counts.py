"""The work a dense decoder's serving steps need, from its shapes alone.

These count what the algorithm needs, not what a program happens to do:

* a prefill of ``S`` tokens runs every layer over ``S`` positions with
  causal attention and the output head at the last position only; it reads
  every weight once and the embedding rows of its tokens, and writes ``S``
  positions of KV cache;
* a decode step of one lane at position ``p`` (the ``p + 1``-th token of
  its sequence) runs every layer and the head for one token, attends over
  ``p + 1`` positions, reads the ``p`` cached ones and writes one.  A step
  reads every weight once, however many lanes are live.

FLOPs are those of the matrix products (2 per multiply-add); norms, RoPE,
softmax and the residual adds are left out.  Bytes are of weights and KV
cache in the served dtype; activations, which stay on chip or are small,
are left out.  A roofline share computed from these is the same whatever
implements the step: a program that reads all ``max_len`` cache positions
or computes the head at every prompt position does more than is counted.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d=d, heads=h,
                   kv_heads=c["num_key_value_heads"], head_dim=d // h,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   qkv_bias=bool(c["qkv_bias"]))

    # -- parameters ----------------------------------------------------------

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's matrix products: q, k, v, o, gate, up,
        down."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.d_ff

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d

    @property
    def stack_weight_bytes(self) -> int:
        """Every weight a step reads: the layers, the final norm and the
        head (the embedding is read by rows, counted per token)."""
        params = self.layers * self.layer_params + self.d + self.d * self.vocab
        return params * self.dtype_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.dtype_bytes

    # -- work ----------------------------------------------------------------

    def prefill(self, s: int) -> tuple[float, float]:
        """(FLOPs, bytes) of a prefill of ``s`` tokens."""
        attn = 2 * self.heads * self.head_dim * s * (s + 1) * self.layers
        flops = 2 * s * self.layers * self.layer_matmul_params + attn \
            + 2 * self.d * self.vocab
        nbytes = self.stack_weight_bytes + s * self.d * self.dtype_bytes \
            + s * self.kv_bytes_per_token
        return float(flops), float(nbytes)

    def decode_lane(self, p: int) -> tuple[float, float]:
        """(FLOPs, bytes) of one lane's step at position ``p``, without the
        step's weight reads (:meth:`decode_steps` adds those once per
        step)."""
        attn = 4 * self.heads * self.head_dim * (p + 1) * self.layers
        flops = 2 * self.layers * self.layer_matmul_params + attn \
            + 2 * self.d * self.vocab
        nbytes = self.d * self.dtype_bytes \
            + (p + 1) * self.kv_bytes_per_token
        return float(flops), float(nbytes)

    def decode_steps(self, steps: int, lane_positions) -> tuple[float, float]:
        """(FLOPs, bytes) of ``steps`` batched decode steps whose live lanes
        stood at ``lane_positions`` (one entry per lane per step)."""
        flops, nbytes = 0.0, float(steps * self.stack_weight_bytes)
        for p in lane_positions:
            f, b = self.decode_lane(p)
            flops += f
            nbytes += b
        return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the least time the chip needs for this work."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
