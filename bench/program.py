"""Where the benchmark reaches the system under test: the program's model
configuration built from a configuration file by its architecture's module,
and the check that the program's parameter tree is the layout
``bench/weights.py`` makes."""
from __future__ import annotations

import dataclasses

from bench import harness, weights


def model_config(config: dict):
    """The program's ``ModelConfig`` holding the file's numbers."""
    cfg = harness.arch(config).model_config(config)
    check_layout(config, cfg)
    return cfg


def norm_eps(cfg, eps: float):
    """``cfg`` with its norms' epsilon ``eps``: set on the program's
    ``ModelConfig`` where it has a ``norm_eps`` field; where it has none, the
    program's norms add a fixed epsilon, and a file stating another is
    refused, since the program would not run what the file states."""
    import inspect
    from repro.models.layers import apply_norm
    if "norm_eps" in {f.name for f in dataclasses.fields(cfg)}:
        return dataclasses.replace(cfg, norm_eps=eps)
    fixed = inspect.signature(apply_norm).parameters["eps"].default
    if eps != fixed:
        raise harness.BenchError(
            f"the configuration states a norm epsilon of {eps!r}; the "
            f"program's norms add a fixed {fixed!r}")
    return cfg


def check_layout(config: dict, cfg) -> None:
    """Refuse a program whose parameter tree differs from the benchmark's
    weights in a name, a shape or an empty group."""
    import jax
    from repro.models import paramlib
    from repro.models.transformer import model_specs
    tree = paramlib.abstract_tree(model_specs(cfg), cfg.param_dtype)
    ours_tree = weights.skeleton(
        config, lambda i, shape, kind, std: jax.ShapeDtypeStruct(
            shape, cfg.param_dtype))

    def shapes(t):
        return {tuple(p.key for p in path): tuple(x.shape) for path, x in
                jax.tree_util.tree_leaves_with_path(t)}
    prog, ours = shapes(tree), shapes(ours_tree)
    if prog != ours:
        raise harness.BenchError(
            f"the program's parameter tree differs from bench/weights.py: "
            f"only the program's {sorted(set(prog.items()) - set(ours.items()))}"
            f", only ours {sorted(set(ours.items()) - set(prog.items()))}")
    if jax.tree.structure(tree) != jax.tree.structure(ours_tree):
        raise harness.BenchError(
            f"the program's parameter tree differs from bench/weights.py in "
            f"its empty groups: {jax.tree.structure(tree)} against "
            f"{jax.tree.structure(ours_tree)}")
