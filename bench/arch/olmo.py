"""OLMo (``model_type`` ``"olmo"``; Groeneveld et al. 2024,
arXiv:2402.00838; the configuration of ``allenai/OLMo-1B-hf``).

The Llama block (``bench/arch/llama.py``: rotary positions, attention with
``num_key_value_heads`` key/value heads, which OLMo sets to the number of
query heads, a SwiGLU MLP and a tied head) with a non-parametric LayerNorm
in place of RMSNorm: the mean and the variance are taken out, and there is
no gain and no bias, so the program's tree holds no weights for its norms
(``norm="layernorm_np"``).  The published configuration states no epsilon;
OLMo's LayerNorm adds 1e-5, which this module takes unless the file gives a
``layer_norm_eps``.

Everything but ``model_config`` imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.arch import llama
from bench.arch.llama import (  # noqa: F401  (the counts are the Llama block's)
    attn_call, attn_flops, kv_bytes, logits_at, matmul_params)

EPS = 1e-5


def dims(config: dict) -> dict:
    return {**llama.shapes(config), "eps": config.get("layer_norm_eps", EPS)}


def layout(config: dict) -> dict:
    return llama.projections(config)


def empty_groups(config: dict) -> tuple:
    return (llama.BLOCK + ("ln1",), llama.BLOCK + ("ln2",), ("final_norm",))


def layer_norm(x, gain, m):
    """Non-parametric LayerNorm; ``gain`` is None."""
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["eps"])


def hidden(w: dict, tokens, m: dict, numerics: str):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    return llama.decoder(w, tokens, m, numerics, layer_norm,
                         {"ln1": None, "ln2": None, "final": None})


def model_config(config: dict):
    """The program's ``ModelConfig`` holding the file's numbers."""
    return llama.program_config(config, "layernorm_np", dims(config)["eps"])
