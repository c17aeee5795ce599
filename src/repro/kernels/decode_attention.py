"""Pallas TPU fused single-token decode attention (GQA, ring-cache aware).

One grid step handles one (batch, kv_head) pair: the whole query-head
*group* that shares a KV head attends at once, so K/V rows are read from
HBM exactly once regardless of the GQA ratio — the memory-bound quantity
for decode.  The kv-cache axis is blocked minor-most with online-softmax
state (m, l, acc) in VMEM scratch, exactly like the prefill flash kernel,
so cache length is bounded only by HBM.

The cache is addressed positionally: callers pass the ``valid`` mask
produced by the ``slot = pos % L`` ring convention
(models/attention.py), so dead slots (not yet written, or outside the
sliding window) are masked here rather than by cache compaction — the
kernel is paged/ring-cache friendly by construction and never needs the
absolute positions.

Layout: q (B, 1, H, hd), k/v (B, L, KV, hd) are transposed to put the kv
head in the grid and the cache axis in blocks; scores per step are
(group, block_k) with group = H // KV.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (group, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    live = valid_ref[0] > 0                             # (1, bk)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]                                 # (group,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    # mask the probabilities too: an all-dead block would otherwise
    # contribute exp(NEG_INF - NEG_INF) = 1 per slot
    p = jnp.exp(s - m_cur[:, None]) * live.astype(jnp.float32)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     valid: jnp.ndarray, block_k: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, hd); k, v: (B, L, KV, hd); valid: (L,) or (B, L) bool
    (per-sequence masks — continuous batching decodes every sequence at
    its own ring position).

    Returns (B, 1, H, hd) in q.dtype.  Matches ref.attention_decode.
    """
    B, _, H, hd = q.shape
    L, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = hd ** -0.5

    block_k = min(block_k, L)
    L_pad = math.ceil(L / block_k) * block_k
    validp = jnp.asarray(valid, jnp.int32)
    if validp.ndim == 1:
        validp = validp[None]
    if L_pad != L:
        pad = ((0, 0), (0, L_pad - L), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        validp = jnp.pad(validp, ((0, 0), (0, L_pad - L)))
    # (B, 1, L_pad): a (1, block_k) block of the two minor dims is a legal
    # TPU tile whatever B is
    validp = jnp.broadcast_to(validp, (B, L_pad))[:, None, :]

    # q: (B, KV, group, hd); k/v: (B, KV, L_pad, hd)
    qt = q.reshape(B, KV, group, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, KV, L_pad // block_k)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, ik: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),          # running max m
            pltpu.VMEM((group,), jnp.float32),          # running sum l
            pltpu.VMEM((group, hd), jnp.float32),       # accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt, validp)

    return out.reshape(B, 1, H, hd)
