"""Operations and bytes of a decoder, from its configuration file.

Only what the algorithm needs is counted: every matrix product once (no
recomputation), causal attention over the positions a query may see, and
weights at the compute dtype.  A count is a floor on the work, so a share of
a peak taken from it cannot pass 100% unless the time leaves work out.  What
depends on the architecture is its module's (``bench/arch/<model_type>.py``);
these names dispatch to it.
"""
from __future__ import annotations

from bench import harness

BYTES = {"bfloat16": 2, "float32": 4}


def matmul_params(config: dict) -> int:
    """Weights that enter a matrix product per token: every layer's
    projections and MLP, and the output head (the embedding lookup is a
    gather, not a product)."""
    return harness.arch(config).matmul_params(config)


def weight_bytes(config: dict) -> int:
    """All weights read once at the compute dtype (a tied embedding is the
    head's weight)."""
    return matmul_params(config) * BYTES[config["compute_dtype"]]


def attn_flops(config: dict, kv_positions: float) -> float:
    """Forward attention of one query over ``kv_positions`` keys, all
    layers."""
    return harness.arch(config).attn_flops(config, kv_positions)


def kv_bytes(config: dict, positions: float) -> float:
    """Keys and values of ``positions`` cached positions, all layers."""
    return harness.arch(config).kv_bytes(config, positions)


def attn_call(config: dict, batch: int, seq: int) -> tuple:
    """(operations, bytes) of one layer's causal attention over ``batch``
    rows of ``seq`` positions, as one flash-attention call computes it."""
    return harness.arch(config).attn_call(config, batch, seq)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward and backward (3x the forward) per trained token: the matrix
    products and causal attention, each query over half the sequence on
    average; recomputation not counted."""
    return 3.0 * (2.0 * matmul_params(config) + attn_flops(config, seq / 2))


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
