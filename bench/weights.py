"""Weights made by the benchmark from the seed, in the program's layout.

The benchmark, not the program, makes the weights: the program is handed
them, and the reference (``bench/reference.py``) makes the same ones again
from the seed, so it takes nothing that the program has made.  The layout
is the program's parameter tree for a dense decoder (names and stacked
shapes); ``program.check_layout`` refuses a run where the program's tree
differs from it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness


def dims(config: dict) -> dict:
    """The shapes of a Llama-style dense decoder (RMSNorm, rotary
    positions, SwiGLU), from a configuration file."""
    if config["model_type"] != "llama":
        raise harness.BenchError(f"no layout for model_type "
                                 f"{config['model_type']!r}")
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    return {
        "d": d, "H": H, "KV": config["num_key_value_heads"],
        "hd": config.get("head_dim") or d // H,
        "f": config["intermediate_size"], "V": config["vocab_size"],
        "L": config["num_hidden_layers"], "eps": config["rms_norm_eps"],
        "theta": config["rope_theta"],
    }


def layout(config: dict) -> dict:
    """{path: (shape, kind, std)} of every weight; kind is ``normal``
    (std given) or ``scale`` (a norm's gain, 1 + 0.1 N(0, 1))."""
    m = dims(config)
    d, H, KV, hd, f, V, L = (m[k] for k in "d H KV hd f V L".split())
    blk = ("groups", "g0", "s0")
    out = {("embedding",): ((V, d), "normal", 0.02)}
    for w, shape in (("wq", (L, d, H * hd)), ("wk", (L, d, KV * hd)),
                     ("wv", (L, d, KV * hd)), ("wo", (L, H * hd, d))):
        out[blk + ("mix", w)] = (shape, "normal", shape[-2] ** -0.5)
    for w, shape in (("wg", (L, d, f)), ("wu", (L, d, f)),
                     ("wd", (L, f, d))):
        out[blk + ("ffn", w)] = (shape, "normal", shape[-2] ** -0.5)
    out[blk + ("ln1", "scale")] = ((L, d), "scale", None)
    out[blk + ("ln2", "scale")] = ((L, d), "scale", None)
    out[("final_norm", "scale")] = ((d,), "scale", None)
    return out


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


@functools.lru_cache(maxsize=4)
def _tree_fn(config_json: str):
    import json
    config = json.loads(config_json)
    spec = sorted(layout(config).items())

    @jax.jit
    def build(key):
        tree: dict = {}
        for i, (path, (shape, kind, std)) in enumerate(spec):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            _set(tree, path, 1.0 + 0.1 * z if kind == "scale" else std * z)
        return tree
    return build


def make(config: dict, seed: int) -> dict:
    """All weights in float32, on the default device, in one jitted call."""
    import json
    build = _tree_fn(json.dumps(config, sort_keys=True))
    return build(harness.seed_key(seed, harness.STREAM_WEIGHTS))


def leaves(tree: dict, prefix: tuple = ()) -> dict:
    """{path: array} of a nested dict's leaves, in sorted path order."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(leaves(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out
