"""Llama-style dense decoder (``model_type`` ``"llama"``).

Pre-norm blocks with RMSNorm gains, rotary positions on the two halves of
each head, grouped-query attention with head ``h`` reading key/value head
``h // (H / KV)``, a SwiGLU MLP, and a head tied to the embedding or, with
``tie_word_embeddings: false``, a head of its own, ``lm_head`` of shape
(d, V) as the program holds it.

Everything but ``model_config`` imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import BYTES
from bench.reference import mm

BLOCK = ("groups", "g0", "s0")


def dims(config: dict) -> dict:
    return {**shapes(config), "eps": config["rms_norm_eps"]}


def shapes(config: dict) -> dict:
    """The sizes of the decoder, and its rotary base."""
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    return {
        "d": d, "H": H, "KV": config["num_key_value_heads"],
        "hd": config.get("head_dim") or d // H,
        "f": config["intermediate_size"], "V": config["vocab_size"],
        "L": config["num_hidden_layers"], "theta": config["rope_theta"],
    }


def layout(config: dict) -> dict:
    out = projections(config)
    m = shapes(config)
    out[BLOCK + ("ln1", "scale")] = ((m["L"], m["d"]), "scale", None)
    out[BLOCK + ("ln2", "scale")] = ((m["L"], m["d"]), "scale", None)
    out[("final_norm", "scale")] = ((m["d"],), "scale", None)
    return out


def projections(config: dict) -> dict:
    """The layout of every weight that enters a matrix product, and the
    embedding."""
    m = shapes(config)
    d, H, KV, hd, f, V, L = (m[k] for k in "d H KV hd f V L".split())
    out = {("embedding",): ((V, d), "normal", 0.02)}
    for w, shape in (("wq", (L, d, H * hd)), ("wk", (L, d, KV * hd)),
                     ("wv", (L, d, KV * hd)), ("wo", (L, H * hd, d))):
        out[BLOCK + ("mix", w)] = (shape, "normal", shape[-2] ** -0.5)
    for w, shape in (("wg", (L, d, f)), ("wu", (L, d, f)),
                     ("wd", (L, f, d))):
        out[BLOCK + ("ffn", w)] = (shape, "normal", shape[-2] ** -0.5)
    if not config["tie_word_embeddings"]:
        out[("lm_head",)] = ((d, V), "normal", d ** -0.5)
    return out


def empty_groups(config: dict) -> tuple:
    return ()


# ---------------------------------------------------------------------------
# The forward, float32 at HIGHEST
# ---------------------------------------------------------------------------

def rms_norm(x, scale, m):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["eps"])
    return x * scale


def rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, lw, m, numerics, norm):
    """One pre-norm block; ``norm(x, gain, m)`` with ``lw``'s ``ln1`` and
    ``ln2`` as the gains."""
    B, S, _ = x.shape
    H, KV, hd = m["H"], m["KV"], m["hd"]
    pos = jnp.arange(S)
    h = norm(x, lw["ln1"], m)
    q = mm("bsd,de->bse", h, lw["wq"], numerics).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, lw["wk"], numerics).reshape(B, S, KV, hd)
    v = mm("bsd,de->bse", h, lw["wv"], numerics).reshape(B, S, KV, hd)
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, numerics) * hd ** -0.5
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, numerics).reshape(B, S, H * hd)
    x = x + mm("bse,ed->bsd", o, lw["wo"], numerics)
    h = norm(x, lw["ln2"], m)
    g = jax.nn.silu(mm("bsd,df->bsf", h, lw["wg"], numerics))
    u = mm("bsd,df->bsf", h, lw["wu"], numerics)
    return x + mm("bsf,fd->bsd", g * u, lw["wd"], numerics)


def decoder(w: dict, tokens, m: dict, numerics: str, norm, gains: dict):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S): the
    blocks scanned over the layers, ``gains`` the norms' ``ln1``, ``ln2``
    and ``final`` gains (None where the norm has none)."""
    blk = w["groups"]["g0"]["s0"]
    per_layer = {"wq": blk["mix"]["wq"], "wk": blk["mix"]["wk"],
                 "wv": blk["mix"]["wv"], "wo": blk["mix"]["wo"],
                 "wg": blk["ffn"]["wg"], "wu": blk["ffn"]["wu"],
                 "wd": blk["ffn"]["wd"], "ln1": gains["ln1"],
                 "ln2": gains["ln2"]}
    x = w["embedding"][tokens].astype(jnp.float32)
    body = jax.checkpoint(
        lambda x, lw: (block(x, lw, m, numerics, norm), None))
    x, _ = jax.lax.scan(body, x, per_layer)
    return norm(x, gains["final"], m)


def hidden(w: dict, tokens, m: dict, numerics: str):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    blk = w["groups"]["g0"]["s0"]
    return decoder(w, tokens, m, numerics, rms_norm,
                   {"ln1": blk["ln1"]["scale"], "ln2": blk["ln2"]["scale"],
                    "final": w["final_norm"]["scale"]})


def logits_at(w: dict, h, numerics: str):
    """Logits (..., V) of hidden states (..., d) through the head."""
    if "lm_head" in w:
        return mm("...d,dv->...v", h, w["lm_head"], numerics)
    return mm("...d,vd->...v", h, w["embedding"], numerics)


# ---------------------------------------------------------------------------
# The program's configuration
# ---------------------------------------------------------------------------

def model_config(config: dict):
    """The program's ``ModelConfig`` holding the file's numbers."""
    return program_config(config, "rmsnorm", config["rms_norm_eps"])


def program_config(config: dict, norm: str, eps: float):
    """The file's numbers over the program's preset ``config["arch"]``,
    with the norm ``norm`` adding ``eps``."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.config import BlockGroup

    from bench import program
    m = shapes(config)
    cfg = dataclasses.replace(
        get_config(config["arch"]),
        groups=(BlockGroup(("attn",), m["L"]),),
        d_model=m["d"], n_heads=m["H"], n_kv_heads=m["KV"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["V"], rope_theta=m["theta"],
        norm=norm, mlp={"silu": "swiglu"}[config["hidden_act"]],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]))
    return program.norm_eps(cfg, eps)


# ---------------------------------------------------------------------------
# Counts (bench/counts.py)
# ---------------------------------------------------------------------------

def matmul_params(config: dict) -> int:
    m = shapes(config)
    d, H, KV, hd, f = m["d"], m["H"], m["KV"], m["hd"], m["f"]
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    return m["L"] * layer + m["V"] * d


def attn_flops(config: dict, kv_positions: float) -> float:
    """q.k and p.v of one query, 2 flops a multiply-add each."""
    m = shapes(config)
    return 4.0 * m["H"] * m["hd"] * m["L"] * kv_positions


def kv_bytes(config: dict, positions: float) -> float:
    m = shapes(config)
    return (2.0 * m["KV"] * m["hd"] * BYTES[config["compute_dtype"]]
            * m["L"] * positions)


def attn_call(config: dict, batch: int, seq: int) -> tuple:
    """4 B H hd S^2 / 2 operations; q, k, v and the output once at the
    compute dtype, k and v at their KV heads."""
    m = shapes(config)
    flops = 4.0 * batch * m["H"] * m["hd"] * seq * seq / 2
    elems = batch * seq * m["hd"] * (2 * m["H"] + 2 * m["KV"])
    return flops, elems * BYTES[config["compute_dtype"]]
