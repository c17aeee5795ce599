"""Trip-count-corrected cost analysis.

``compiled.cost_analysis()`` visits every HLO instruction ONCE — a
``jax.lax.scan`` over n layers reports the flops/bytes/collectives of a
single layer (verified empirically: scan n=1/4/16 of the same body all
report identical flops).  Every roofline term would be undercounted by
~n_layers without correction.

Correction: for each block group g we lower the *per-layer body* standalone
(same shardings, same remat structure: fwd for inference paths,
fwd + remat-fwd + bwd via ``jax.grad(checkpoint(body))`` for training — the
exact per-layer work the scanned forward+backward executes) and add
``(n_g - 1) x body_cost`` to the full-module measurement:

    corrected = full_module + sum_g (n_g - 1) * body_g

The correction is validated against a fully-unrolled lowering of the
smallest arch in tests/test_costmodel.py (agreement within a few percent).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .sharding import activation_rules
from ..models import paramlib
from ..models.config import ModelConfig
from ..models.transformer import (Ctx, _apply_block, _decode_block,
                                  _prefill_block, _remat_wrap, model_specs)
from .sharding import resolve_spec, tree_shardings

SDS = jax.ShapeDtypeStruct


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()``: the per-device dict, or {} when the
    backend reports none."""
    return compiled.cost_analysis() or {}


def _slot_specs_for_group(cfg: ModelConfig, gi: int):
    """Abstract per-layer (leading scan dim removed) params for group gi,
    with matching shardings."""
    specs = model_specs(cfg)
    group_specs = specs["groups"][f"g{gi}"]
    sliced = jax.tree.map(
        lambda p: paramlib.P(p.shape[1:], p.axes[1:], p.init, p.scale,
                             p.fan_in_dim, p.dtype),
        group_specs, is_leaf=lambda x: isinstance(x, paramlib.P))
    abs_tree = paramlib.abstract_tree(sliced, cfg.param_dtype)
    axes = paramlib.axes_tree(sliced)
    return abs_tree, axes


def _media_abs(cfg: ModelConfig, B: int):
    if cfg.frontend == "vision":
        return SDS((B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
    return None


def group_body_cost(cfg: ModelConfig, gi: int, mesh, rules, kind: str,
                    B: int, S: int, remat: str,
                    parse_collectives) -> dict:
    """Lower one group's per-layer body; returns its cost terms.
    kind: 'train' | 'prefill' | 'decode'."""
    g = cfg.groups[gi]
    abs_params, axes = _slot_specs_for_group(cfg, gi)
    p_shard = tree_shardings(axes, abs_params, mesh, rules)

    from jax.sharding import NamedSharding, PartitionSpec as PS
    import os as _os
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp = dp if len(dp) > 1 else dp[0]
    seq = 1 if kind == "decode" else S
    x_abs = SDS((B, seq, cfg.d_model), cfg.dtype)
    if _os.environ.get("REPRO_SP") == "1" and kind != "decode":
        # sequence parallelism: body I/O seq-sharded over `model`, matching
        # the full-module act constraint
        x_spec = PS(dp, "model", None)
    else:
        x_spec = resolve_spec(("batch", None, None), x_abs.shape, mesh,
                              activation_rules())
    x_shard = NamedSharding(mesh, x_spec)
    media = _media_abs(cfg, B)
    media_shard = NamedSharding(mesh, resolve_spec(
        ("batch", None, None), media.shape, mesh,
        activation_rules())) if media is not None else None

    pos = jnp.arange(seq)[None]

    if kind == "train":
        def inner(x, slot_params, media_v):
            ctx = Ctx(positions=jnp.broadcast_to(pos, (x.shape[0], seq)),
                      media=media_v)
            for si, k in enumerate(g.pattern):
                x, _ = _apply_block(slot_params[f"s{si}"], k, x, cfg, ctx)
            return x

        wrapped = _remat_wrap(inner, remat if remat != "none" else "none")

        # In the scanned execution the forward pass (fwd scan) and the
        # remat-fwd + bwd (bwd scan) live in SEPARATE while loops, so the
        # remat genuinely re-executes.  A standalone value_and_grad lowering
        # would let XLA CSE the primal fwd with the remat fwd and undercount
        # by one forward.  So for remat policies we measure grad-only (DCE
        # drops the unused primal -> remat-fwd + bwd) and add a separate
        # fwd-only lowering.
        def body_grad(x, ct, slot_params, media_v):
            # data-dependent cotangent: prevents XLA constant-folding the
            # backward matmuls (ones-cotangent loses ~half the bwd flops)
            def lossy(xx, pp):
                return jnp.vdot(wrapped(xx, pp, media_v)
                                .astype(jnp.float32), ct)
            if remat == "none":
                return jax.value_and_grad(lossy, argnums=(0, 1))(
                    x, slot_params)
            return jax.grad(lossy, argnums=(0, 1))(x, slot_params)

        body = body_grad
        extra_fwd = (inner if remat != "none" else None)
        ct_abs = SDS((B, seq, cfg.d_model), jnp.float32)
        args = (x_abs, ct_abs, abs_params, media)
        shardings = (x_shard, x_shard, p_shard, media_shard)
    elif kind == "prefill":
        def body(x, slot_params, media_v):
            ctx = Ctx(positions=jnp.broadcast_to(pos, (x.shape[0], seq)),
                      media=media_v)
            out = x
            caches = []
            for si, k in enumerate(g.pattern):
                out, c = _prefill_block(slot_params[f"s{si}"], k, out, cfg,
                                        ctx, S)
                caches.append(c)
            return out, caches
        args = (x_abs, abs_params, media)
        shardings = (x_shard, p_shard, media_shard)
    else:  # decode
        from ..models.transformer import init_cache, cache_axes
        full_cache = init_cache(cfg, B, S, abstract=True)
        full_axes = cache_axes(cfg)
        slot_cache = jax.tree.map(
            lambda sds: SDS(sds.shape[1:], sds.dtype),
            full_cache[f"g{gi}"])
        slot_cache_axes = jax.tree.map(
            lambda ax: ax[1:], full_axes[f"g{gi}"],
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        c_shard = tree_shardings(slot_cache_axes, slot_cache, mesh,
                                 activation_rules())

        def body(x, slot_params, slot_cache_v, media_v):
            ctx = Ctx(positions=jnp.full((x.shape[0], 1), S - 1),
                      media=media_v)
            out = x
            new = {}
            for si, k in enumerate(g.pattern):
                out, nc = _decode_block(slot_params[f"s{si}"], k, out,
                                        slot_cache_v[f"s{si}"], cfg,
                                        jnp.asarray(S - 1, jnp.int32), ctx)
                new[f"s{si}"] = nc
            return out, new
        args = (x_abs, abs_params, slot_cache, media)
        shardings = (x_shard, p_shard, c_shard, media_shard)

    # drop None media arg for non-vision models (jit dislikes None shardings
    # paired with None args only in older versions; keep it simple)
    if media is None:
        def body2(*a):
            return body(*a, None)
        args = args[:-1]
        shardings = shardings[:-1]
    else:
        body2 = body

    with mesh:
        compiled = jax.jit(body2, in_shardings=shardings) \
            .lower(*args).compile()
        cost = cost_dict(compiled)
        coll = parse_collectives(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))

    if kind == "train" and extra_fwd is not None:
        # add the primal forward the fwd scan executes (see comment above)
        def fwd_only(x, slot_params, media_v):
            return extra_fwd(x, slot_params, media_v)
        fargs = (x_abs, abs_params) + ((media,) if media is not None else ())
        fshard = (x_shard, p_shard) + ((media_shard,)
                                       if media is not None else ())
        if media is None:
            def fwd2(x, p):
                return fwd_only(x, p, None)
        else:
            fwd2 = fwd_only
        with mesh:
            fcomp = jax.jit(fwd2, in_shardings=fshard) \
                .lower(*fargs).compile()
            fcost = cost_dict(fcomp)
            fcoll = parse_collectives(fcomp.as_text())
        flops += float(fcost.get("flops", 0.0))
        byts += float(fcost.get("bytes accessed", 0.0))
        for k, v in fcoll.items():
            coll[k] = coll.get(k, 0.0) + v

    return {"flops": flops, "bytes": byts, "collectives": coll, "n": g.n}


def corrected_terms(full_result: dict, body_costs: list[dict]) -> dict:
    """full_module + sum_g (n_g - 1) * body_g for every term."""
    flops = full_result["cost"]["flops_per_device"]
    byts = full_result["cost"]["bytes_per_device"]
    coll = dict(full_result.get("collectives", {}))
    for b in body_costs:
        k = b["n"] - 1
        if k <= 0:
            continue
        flops += k * b["flops"]
        byts += k * b["bytes"]
        for kind, v in b["collectives"].items():
            coll[kind] = coll.get(kind, 0.0) + k * v
    return {"flops_per_device": flops, "bytes_per_device": byts,
            "collectives": coll}
