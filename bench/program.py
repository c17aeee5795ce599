"""Where the benchmark reaches the system under test: the program's model
configuration built from a configuration file, and the check that the
program's parameter tree is the layout ``bench/weights.py`` makes."""
from __future__ import annotations

import dataclasses

from bench import harness, weights


def model_config(config: dict):
    """The program's ``ModelConfig`` holding the file's numbers."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.config import BlockGroup
    m = weights.dims(config)
    cfg = dataclasses.replace(
        get_config(config["arch"]),
        groups=(BlockGroup(("attn",), m["L"]),),
        d_model=m["d"], n_heads=m["H"], n_kv_heads=m["KV"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["V"], rope_theta=m["theta"],
        norm="rmsnorm", mlp={"silu": "swiglu"}[config["hidden_act"]],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]))
    if not cfg.tie_embeddings:
        raise harness.BenchError("the reference ties the head to the "
                                 "embedding; this configuration does not")
    check_layout(config, cfg)
    return cfg


def check_layout(config: dict, cfg) -> None:
    """Refuse a program whose parameter tree differs from the benchmark's
    weights in a name or a shape."""
    import jax
    from repro.models import paramlib
    from repro.models.transformer import model_specs
    prog = jax.tree_util.tree_leaves_with_path(
        paramlib.abstract_tree(model_specs(cfg), cfg.param_dtype))
    prog = {tuple(p.key for p in path): tuple(x.shape) for path, x in prog}
    ours = {path: shape for path, (shape, _, _) in
            weights.layout(config).items()}
    if prog != ours:
        raise harness.BenchError(
            f"the program's parameter tree differs from bench/weights.py: "
            f"only the program's {sorted(set(prog.items()) - set(ours.items()))}"
            f", only ours {sorted(set(ours.items()) - set(prog.items()))}")
