"""Logical-axis -> mesh-axis sharding resolution.

A tensor's dims carry logical names (see repro.core.sync_jax); rules map each
name to an ordered list of candidates (a mesh axis or tuple of axes).  The
first candidate whose total size divides the dim and whose mesh axes are not
already used by another dim of the same tensor wins; otherwise the dim is
replicated.  This gives automatic, divisibility-safe fallbacks — e.g. a KV
cache with 8 kv-heads on a 16-way model axis silently falls back to
sequence-parallel (kv_seq -> model) sharding.
"""
from __future__ import annotations

import math
import os
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from ..core.sync_jax import ACTIVATION_RULES

Rules = Mapping[str, Sequence[Any]]


def activation_rules() -> Rules:
    """Activation rules, honoring the REPRO_DP_OVER_MODEL=1 experiment
    toggle: use the `model` axis as additional data parallelism (small
    dense archs whose TP all-reduces dominate — see EXPERIMENTS.md §Perf).
    The parameter database stays sharded over `data` (the paper technique
    is orthogonal to this choice)."""
    if os.environ.get("REPRO_DP_OVER_MODEL") == "1":
        return {**ACTIVATION_RULES,
                "batch": (("pod", "data", "model"), ("data", "model"),
                          ("data",))}
    return ACTIVATION_RULES


def _axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def resolve_spec(logical_axes: Sequence[str | None],
                 shape: Sequence[int], mesh: Mesh, rules: Rules) -> PS:
    spec: list[Any] = []
    used: set[str] = set()
    for dim, name in zip(shape, logical_axes):
        choice = None
        if name is not None:
            for cand in rules.get(name, ()):
                axes = (cand,) if isinstance(cand, str) else tuple(cand)
                if not all(a in mesh.shape for a in axes):
                    continue
                if set(axes) & used:
                    continue
                if dim % _axis_size(mesh, axes) != 0:
                    continue
                choice = axes[0] if len(axes) == 1 else axes
                used.update(axes)
                break
        spec.append(choice)
    return PS(*spec)


def tree_shardings(axes_tree, abstract_tree, mesh: Mesh, rules: Rules):
    """NamedSharding tree for a (axes, ShapeDtypeStruct) tree pair."""
    return jax.tree.map(
        lambda ax, sds: NamedSharding(
            mesh, resolve_spec(ax, sds.shape, mesh, rules)),
        axes_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def batch_shardings(batch_axes: dict, batch_abstract: dict, mesh: Mesh,
                    rules: Rules | None = None):
    rules = rules or activation_rules()
    return {
        k: NamedSharding(mesh, resolve_spec(batch_axes[k],
                                            batch_abstract[k].shape,
                                            mesh, rules))
        for k in batch_abstract}


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PS())


def opt_state_shardings(param_shardings, opt_state_abstract, mesh: Mesh):
    """m/v mirror the parameter shardings; scalars replicate."""
    def pick(path, sds):
        if sds.ndim == 0:
            return replicated(mesh)
        # path like ('m', <param path...>) — look up the param sharding
        key = path[0].key if hasattr(path[0], "key") else str(path[0])
        if key in ("m", "v", "mom", "residual"):
            sub = param_shardings
            for p in path[1:]:
                k = getattr(p, "key", None)
                sub = sub[k] if k is not None else sub[p.idx]
            return sub
        return replicated(mesh)
    return jax.tree_util.tree_map_with_path(pick, opt_state_abstract)


def make_act_specs(mesh, sp: bool = False) -> dict:
    """Activation sharding constraints: block I/O sharded over the dp axes;
    logits (and the CE one-hot) additionally vocab-sharded over `model` —
    without this the softmax/one-hot temporaries replicate the vocab dim.

    sp=True additionally shards the SEQUENCE dim of block I/O over `model`
    (Megatron-style sequence parallelism): norms/residuals/embeddings run
    seq-sharded and GSPMD inserts all-gathers only where attention needs the
    full sequence — the fix for archs whose head count cannot shard over the
    model axis (smollm: 15 heads on a 16-way axis)."""
    if os.environ.get("REPRO_DP_OVER_MODEL") == "1":
        dp = (("pod", "data", "model") if "pod" in mesh.shape
              else ("data", "model"))
        return {"act": NamedSharding(mesh, PS(dp, None, None)),
                "logits": NamedSharding(mesh, PS(dp, None, None))}
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp = dp if len(dp) > 1 else dp[0]
    seq_ax = "model" if sp else None
    # logits keep vocab (not seq) on `model` — one axis, one dim
    return {"act": NamedSharding(mesh, PS(dp, seq_ax, None)),
            "logits": NamedSharding(mesh, PS(dp, None, "model"))}
