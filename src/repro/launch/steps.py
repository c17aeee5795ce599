"""Step-function builders shared by train.py, serve.py and dryrun.py.

``make_train_step`` supports the two synchronization modes (the sharding
difference is applied by the caller via in_shardings — see sync_jax) and
the delta-staleness engine; the step function itself is mode-agnostic pure
dataflow, exactly as Theorem 2 requires: correctness is enforced by the
read/write (all-gather / reduce-scatter) dependency structure, not by the
step code.
"""
from __future__ import annotations

import os
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..pdb.jax_backend import TrainEngine, make_engine
from ..core.sync_jax import SyncConfig
from ..models import paramlib
from ..models.config import ModelConfig
from ..models.transformer import decode_step as model_decode
from ..models.transformer import lm_loss, model_specs, prefill
from ..optim.optimizers import Optimizer
from .sharding import (batch_shardings, make_act_specs, opt_state_shardings,
                       tree_shardings)


def make_train_step(cfg: ModelConfig, opt: Optimizer, sync: SyncConfig,
                    act_specs: dict | None = None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, batch, cfg, remat=sync.remat,
                                   act_specs=act_specs)
        new_params, new_opt = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        return new_params, new_opt, metrics
    return train_step


def make_sharded_train_step(cfg: ModelConfig, opt: Optimizer,
                            sync: SyncConfig, mesh, batch_abs: dict,
                            batch_axes: dict):
    """The SPMD train step on ``mesh``: parameters laid out by the sync
    mode's rules (``datacentric`` shards them over ``data``, ``bsp``
    replicates them), optimizer state mirroring them, batch over the data
    axes; parameters and optimizer state donated.

    Returns (jitted step, abstract params, abstract optimizer state,
    (param, optimizer-state, batch) shardings)."""
    specs = model_specs(cfg)
    params_abs = paramlib.abstract_tree(specs, cfg.param_dtype)
    p_shard = tree_shardings(paramlib.axes_tree(specs), params_abs, mesh,
                             sync.param_rules)
    act_specs = make_act_specs(mesh, sp=os.environ.get("REPRO_SP") == "1")
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_shard = opt_state_shardings(p_shard, opt_abs, mesh)
    b_shard = batch_shardings(batch_axes, batch_abs, mesh)
    fn = jax.jit(make_train_step(cfg, opt, sync, act_specs=act_specs),
                 in_shardings=(p_shard, o_shard, b_shard),
                 out_shardings=(p_shard, o_shard, None),
                 donate_argnums=(0, 1))
    return fn, params_abs, opt_abs, (p_shard, o_shard, b_shard)


def make_lm_grad_fn(cfg: ModelConfig, sync: SyncConfig) -> Callable:
    """grad_fn(params, batch) -> (loss, grads) over the LM loss."""
    def grad_fn(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, batch, cfg, remat=sync.remat)
        return loss, grads
    return grad_fn


def make_train_engine(cfg: ModelConfig, opt: Optimizer, sync: SyncConfig,
                      params: Any, record_history: bool = False) -> TrainEngine:
    """The unified ParameterDB train engine (both sync and delayed paths)
    used by the training driver; see :mod:`repro.pdb.jax_backend`."""
    return make_engine(params, make_lm_grad_fn(cfg, sync), opt, sync,
                       record_history=record_history)


def make_prefill_step(cfg: ModelConfig, cache_len: int,
                      remat: str = "none",
                      act_specs: dict | None = None) -> Callable:
    def prefill_step(params, batch):
        return prefill(params, batch["tokens"], cfg, cache_len=cache_len,
                       media=batch.get("media"), remat=remat,
                       act_specs=act_specs)
    return prefill_step


def make_decode_step(cfg: ModelConfig,
                     act_specs: dict | None = None) -> Callable:
    def serve_step(params, cache, batch):
        logits, new_cache = model_decode(params, cache, batch["tokens"],
                                         batch["pos"], cfg,
                                         media=batch.get("media"),
                                         act_specs=act_specs)
        return logits, new_cache
    return serve_step
