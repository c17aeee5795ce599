"""Weights made by the benchmark from the seed, in the program's layout.

The benchmark, not the program, makes the weights: the program is handed
them, and the reference (``bench/reference.py``) makes the same ones again
from the seed, so it takes nothing that the program has made.  The layout
is the program's parameter tree (names and stacked shapes), given by the
configuration's architecture module; ``program.check_layout`` refuses a run
where the program's tree differs from it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness


def dims(config: dict) -> dict:
    """The shapes of the configuration's architecture
    (``bench/arch/<model_type>.py``)."""
    return harness.arch(config).dims(config)


def layout(config: dict) -> dict:
    """{path: (shape, kind, std)} of every weight; kind is ``normal``
    (std given) or ``scale`` (a norm's gain, 1 + 0.1 N(0, 1))."""
    return harness.arch(config).layout(config)


def skeleton(config: dict, leaf) -> dict:
    """The program's parameter tree of the configuration, ``leaf(i, shape,
    kind, std)`` at the ``i``-th weight in sorted path order, and an empty
    dict at each group that holds no weights."""
    tree: dict = {}
    for path in harness.arch(config).empty_groups(config):
        _set(tree, path, {})
    for i, (path, spec) in enumerate(sorted(layout(config).items())):
        _set(tree, path, leaf(i, *spec))
    return tree


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


@functools.lru_cache(maxsize=4)
def _tree_fn(config_json: str):
    import json
    config = json.loads(config_json)

    @jax.jit
    def build(key):
        def leaf(i, shape, kind, std):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            return 1.0 + 0.1 * z if kind == "scale" else std * z
        return skeleton(config, leaf)
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
