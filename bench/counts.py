"""Operations and bytes of a dense decoder, from its configuration file.

Only what the algorithm needs is counted: every matrix product once (no
recomputation), causal attention over the positions a query may see, and
weights at the compute dtype.  A count is a floor on the work, so a share of
a peak taken from it cannot pass 100% unless the time leaves work out.
"""
from __future__ import annotations

from bench import weights

BYTES = {"bfloat16": 2, "float32": 4}


def matmul_params(config: dict) -> int:
    """Weights that enter a matrix product per token: every layer's
    projections and MLP, and the output head (the embedding lookup is a
    gather, not a product)."""
    m = weights.dims(config)
    d, H, KV, hd, f = m["d"], m["H"], m["KV"], m["hd"], m["f"]
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    return m["L"] * layer + m["V"] * d


def weight_bytes(config: dict) -> int:
    """All weights read once at the compute dtype (the tied embedding is
    the head's weight)."""
    return matmul_params(config) * BYTES[config["compute_dtype"]]


def attn_flops(config: dict, kv_positions: float) -> float:
    """Forward attention of one query over ``kv_positions`` keys, all
    layers: q.k and p.v, 2 flops a multiply-add each."""
    m = weights.dims(config)
    return 4.0 * m["H"] * m["hd"] * m["L"] * kv_positions


def kv_bytes(config: dict, positions: float) -> float:
    """Keys and values of ``positions`` cached positions, all layers."""
    m = weights.dims(config)
    return (2.0 * m["KV"] * m["hd"] * BYTES[config["compute_dtype"]]
            * m["L"] * positions)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward and backward (3x the forward) per trained token: the matrix
    products and causal attention, each query over half the sequence on
    average; recomputation not counted."""
    return 3.0 * (2.0 * matmul_params(config) + attn_flops(config, seq / 2))


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
