"""Pallas page-table gather for the paged serving KV cache.

The serving engine (repro.serve) keeps each attention layer's KV cache as
a pool of fixed-size pages shared by all sequence slots; a per-sequence
page table maps logical cache pages to physical pool pages
(vLLM-style paged attention, restricted to gather-before-attend).

Materializing the logical (B, L, KV, hd) view is then a gather of
``B * pages_per_seq`` whole pages.  Like kernels/ring_gather.py, the page
ids arrive through scalar prefetch (``PrefetchScalarGridSpec``) so the
BlockSpec index map itself selects the physical page: the gather is pure
DMA, one grid step per page — no compute, no scatter, regardless of how
fragmented the page table is.  A block is one whole page in the pool's
own layout, so its two minor dims are the array's and the TPU tiling
accepts it for any page size and head shape.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _gather_kernel(pt_ref, pool_ref, out_ref):
    del pt_ref  # consumed by the BlockSpec index maps
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather(pool: jnp.ndarray, page_table: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    """pool: (P, page, *tail) with at least one tail dim; page_table:
    (B, n_pp) int32 in [0, P).

    Returns the logical view (B, n_pp * page, *tail) in pool dtype, i.e.
    ``pool[page_table]`` with the page axis folded into the cache axis.
    """
    page, tail = pool.shape[1], pool.shape[2:]
    B, n_pp = page_table.shape
    idx = page_table.reshape(-1).astype(jnp.int32)        # (B * n_pp,)
    block = (1, page) + tail
    zeros = (0,) * (len(tail) + 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * n_pp,),
        in_specs=[pl.BlockSpec(block, lambda i, pt_ref: (pt_ref[i],) + zeros)],
        out_specs=pl.BlockSpec(block, lambda i, pt_ref: (i,) + zeros),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * n_pp,) + pool.shape[1:],
                                       pool.dtype),
        interpret=interpret,
    )(idx, pool)
    return out.reshape((B, n_pp * page) + tail)


def _prefill_kernel(q_ref, k_ref, v_ref, kpos_ref, qpos_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, scale: float, window: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (R, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = kpos_ref[0]                                  # (1, bk)
    qp = qpos_ref[0]                                    # (R, 1)
    live = (kpos >= 0) & (kpos <= qp)
    if window > 0:
        live &= kpos > qp - window
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]                                 # (R,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    # re-mask probabilities: an all-dead block would otherwise
    # contribute exp(NEG_INF - NEG_INF) = 1 per slot
    p = jnp.exp(s - m_cur[:, None]) * live.astype(jnp.float32)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_k", "interpret"))
def prefill_page_attention(q: jnp.ndarray, k_ctx: jnp.ndarray,
                           v_ctx: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, ctx_pos: jnp.ndarray,
                           q_pos: jnp.ndarray, window: int = 0,
                           block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """Chunked-prefill flash attention over a gathered paged context.

    q, k_new, v_new: (B, C, H|KV, hd) current chunk; k_ctx, v_ctx:
    (B, L, KV, hd) logical ring view of prior chunks (page_gather
    output); ctx_pos: (B, L) int32 absolute position per ring slot
    (negative = dead); q_pos: (B, C) int32 chunk-token positions.

    One grid step covers one (batch, kv_head) pair with the whole
    chunk's query-head group flattened into rows, the concatenated
    ctx+chunk key axis blocked minor-most with online-softmax scratch —
    the chunk-sized generalization of decode_attention, masked by
    absolute position (0 <= kpos <= qpos, plus sliding window) instead
    of a precomputed valid vector.  Matches ref.prefill_page_attention.
    """
    B, C, H, hd = q.shape
    L, KV = k_ctx.shape[1], k_ctx.shape[2]
    group = H // KV
    scale = hd ** -0.5

    k = jnp.concatenate([k_ctx, k_new.astype(k_ctx.dtype)], axis=1)
    v = jnp.concatenate([v_ctx, v_new.astype(v_ctx.dtype)], axis=1)
    kpos = jnp.concatenate([ctx_pos, q_pos], axis=1).astype(jnp.int32)
    T = L + C
    block_k = min(block_k, T)
    T_pad = math.ceil(T / block_k) * block_k
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, T_pad - T)), constant_values=-1)

    # queries: (B, C, H, hd) -> (B, KV, group * C, hd); row r is
    # (head kv*group + r // C, chunk token r % C)
    R = group * C
    R_pad = math.ceil(R / 8) * 8
    qt = q.transpose(0, 2, 1, 3).reshape(B, KV, R, hd)
    qpos_row = jnp.tile(q_pos.astype(jnp.int32), (1, group))  # (B, R)
    if R_pad != R:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, R_pad - R), (0, 0)))
        qpos_row = jnp.pad(qpos_row, ((0, 0), (0, R_pad - R)),
                           constant_values=-1)
    kt = k.transpose(0, 2, 1, 3)                        # (B, KV, T_pad, hd)
    vt = v.transpose(0, 2, 1, 3)
    # key positions as rows and query positions as columns, so each block's
    # two minor dims are legal TPU tiles and the mask needs no transpose
    kpos = kpos[:, None, :]                             # (B, 1, T_pad)
    qpos_row = qpos_row[:, :, None]                     # (B, R_pad, 1)

    grid = (B, KV, T_pad // block_k)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, R_pad, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, ik: (b, 0, ik)),
            pl.BlockSpec((1, R_pad, 1), lambda b, h, ik: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, R_pad, hd),
                               lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, R_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((R_pad,), jnp.float32),          # running max m
            pltpu.VMEM((R_pad,), jnp.float32),          # running sum l
            pltpu.VMEM((R_pad, hd), jnp.float32),       # accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt, kpos, qpos_row)

    out = out[:, :, :R].reshape(B, KV, group, C, hd)
    return out.reshape(B, H, C, hd).transpose(0, 2, 1, 3)
