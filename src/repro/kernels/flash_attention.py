"""Pallas TPU flash attention (causal + sliding window, GQA-aware).

Tiling: grid = (batch, kv_heads, q_blocks, kv_blocks); the kv_blocks axis
is minor-most, so on TPU it iterates sequentially per (b, kv, iq) and the
online softmax state (m, l, acc) lives in VMEM scratch across kv
iterations.  One grid row holds every query head of a KV group: the q
block is (1, group, bq, hd) and the kernel stacks it to (group * bq, hd)
rows against one K/V tile, so each K/V tile is copied in once per group.

Steps that the causal mask or the window leave empty skip their compute,
and their K/V index map is clamped to the nearest block the query block
sees, so the pipeline names a block that is already resident and copies
nothing.  Products run at the input dtype with float32 accumulation (the
reference's precision); the running max and sum are lane-dense
(rows, 128) float32 scratch.

Block sizes follow from the input (``choose_blocks``): the query block is
the whole padded sequence up to 1,024 and 512 beyond it; the key block is
256 under a causal mask (finer skipping on the diagonal) and 512 without
one, no wider than the window, each shrunk until the step's working set
(``vmem_bytes``) fits ``VMEM_BUDGET``.  At SmolLM-360M's training shape
(batch 8, S 1,024, 15/5 heads of 64, bf16) that is bq 512, bk 256: a
(3 x 512, 256) float32 score tile, 8 x 5 x 2 x 4 = 320 grid steps a call,
80 of them masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# under v5e's 16 MiB default scoped VMEM, with room for compiler temporaries
VMEM_BUDGET = 12 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(group: int, block_q: int, block_k: int, hd: int,
               itemsize: int) -> int:
    """Working set of one grid step: q, o, k, v blocks double-buffered,
    the m/l/acc scratch and three (rows, block_k) float32 score tiles."""
    hd_vmem = _round_up(hd, LANES)          # VMEM tiles pad the lane axis
    rows = group * block_q
    blocks = 2 * (2 * rows + 2 * block_k) * hd_vmem * itemsize
    scratch = rows * (2 * LANES + hd_vmem) * 4
    scores = 3 * rows * block_k * 4
    return blocks + scratch + scores


def choose_blocks(seq_len: int, hd: int, group: int, dtype,
                  causal: bool = True, window: int = 0) -> tuple[int, int]:
    """(block_q, block_k) for a sequence padded to a multiple of 128: both
    multiples of 128 that divide the padded length, block_q a multiple of
    block_k, the largest that fit ``VMEM_BUDGET``."""
    s = _round_up(seq_len, LANES)
    itemsize = jnp.dtype(dtype).itemsize
    k_target = 256 if causal else 512
    if window > 0:
        k_target = min(k_target, max(LANES, window))
    q_target = s if s <= 1024 else 512
    for bk in (512, 256, 128):
        if bk > k_target or s % bk:
            continue
        for bq in range(min(q_target, s) // bk * bk, 0, -bk):
            if s % bq == 0 and vmem_bytes(group, bq, bk, hd,
                                          itemsize) <= VMEM_BUDGET:
                return bq, bk
    return LANES, LANES


def _kv_range(iq, block_q: int, block_k: int, seq_len: int, causal: bool,
              window: int):
    """First and last key blocks that query block ``iq`` sees."""
    q_start = iq * block_q
    hi = (seq_len - 1) // block_k
    if causal:
        hi = jnp.minimum(hi, (q_start + block_q - 1) // block_k)
    lo = 0
    if window > 0:
        lo = jnp.maximum(q_start - window + 1, 0) // block_k
    return lo, hi


def _lanes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """A lane-replicated (rows, 128) statistic cut or tiled to n lanes (n
    under 128 or a multiple of it)."""
    return x[:, :n] if n <= LANES else jnp.tile(x, (1, n // LANES))


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int,
                 block_q: int, block_k: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    _, group, _, hd = q_ref.shape
    rows = group * block_q

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo, hi = _kv_range(iq, block_q, block_k, seq_len, causal, window)

    @pl.when((ik >= lo) & (ik <= hi))
    def _compute():
        q = q_ref[0].reshape(rows, hd)                  # group heads stacked
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rows, bk)

        shape = (block_q, block_k)
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None], s.reshape(group, *shape), NEG_INF)
        s = s.reshape(rows, block_k)

        m_prev = m_scr[...]                             # (rows, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, block_k))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = _lanes(alpha, hd) * acc_scr[...] + pv
        m_scr[...] = m_next

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = _lanes(jnp.maximum(l_scr[...], 1e-30), hd)
        out = acc_scr[...] / denom
        o_ref[0] = out.reshape(group, block_q, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) -> (B, S, H, hd).  Unless
    both blocks are given, they are chosen from the shape
    (``choose_blocks``)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    scale = hd ** -0.5

    if block_q is None or block_k is None:
        block_q, block_k = choose_blocks(S, hd, group, q.dtype, causal,
                                         window)
    else:
        block_q, block_k = min(block_q, S), min(block_k, S)
    # pad sequence to block multiples (kernel masks the tail)
    S_pad = _round_up(S, math.lcm(block_q, block_k))
    if S_pad != S:
        pad = ((0, 0), (0, S_pad - S), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # (B, heads, S, hd) layout: a KV group's query heads are adjacent
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kv_range = functools.partial(_kv_range, block_q=block_q, block_k=block_k,
                                 seq_len=S, causal=causal, window=window)

    def kv_index_map(b, h, iq, ik):
        # a step outside [lo, hi] names the block already resident: no copy
        lo, hi = kv_range(iq)
        return (b, h, jnp.minimum(jnp.maximum(ik, lo), hi), 0)

    q_spec = pl.BlockSpec((1, group, block_q, hd),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), kv_index_map)
    rows = group * block_q

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_len=S),
        grid=(B, KV, S_pad // block_q, S_pad // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),   # running max m
            pltpu.VMEM((rows, LANES), jnp.float32),   # running sum l
            pltpu.VMEM((rows, hd), jnp.float32),      # accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)

    return out.transpose(0, 2, 1, 3)[:, :S]
