"""Jit'd dispatch layer over the Pallas kernels and their jnp references.

The models call only these entry points.  Implementation choice:

  * ``REPRO_KERNEL_IMPL=ref``      (default) — XLA path; used on CPU, in the
    dry-run lowering, and anywhere Pallas-to-backend lowering is unavailable.
  * ``REPRO_KERNEL_IMPL=pallas``   — the Pallas TPU kernels (real hardware).
  * ``REPRO_KERNEL_IMPL=interpret`` — Pallas kernels in interpret mode
    (Python emulation on CPU; what the kernel tests use).

Both paths compute identical math — tests/test_kernels.py sweeps shapes and
dtypes asserting allclose between them.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref

_VALID = ("ref", "pallas", "interpret")


def kernel_impl() -> str:
    impl = os.environ.get("REPRO_KERNEL_IMPL", "ref")
    if impl not in _VALID:
        raise ValueError(f"REPRO_KERNEL_IMPL={impl!r}; want one of {_VALID}")
    return impl


# sequences at or above this length take the blockwise XLA path (bounded
# score-matrix memory); below it the plain path fuses better
CHUNK_THRESHOLD = int(os.environ.get("REPRO_ATTN_CHUNK_THRESHOLD", 4096))


def _ref_attention(q, k, v, causal, window):
    S = q.shape[1]
    if S >= CHUNK_THRESHOLD and S % min(1024, S) == 0 \
            and q.shape[1] == k.shape[1]:
        return ref.attention_chunked(q, k, v, causal=causal, window=window)
    return ref.attention(q, k, v, causal=causal, window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, interpret):
    from .flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=interpret)


def _flash_fwd(q, k, v, causal, window, interpret):
    return _flash(q, k, v, causal, window, interpret), (q, k, v)


def _flash_bwd(causal, window, interpret, res, g):
    # the Pallas kernel has no backward pass: the gradient differentiates
    # the XLA reference, recomputed from the saved q, k, v
    _, vjp = jax.vjp(
        lambda q, k, v: _ref_attention(q, k, v, causal, window), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, window: int = 0,
              impl: str | None = None) -> jnp.ndarray:
    """Differentiable on every impl: the Pallas forward pairs with the
    reference's backward."""
    impl = impl or kernel_impl()
    if impl == "ref":
        return _ref_attention(q, k, v, causal, window)
    return _flash(q, k, v, causal, window, impl == "interpret")


def attention_decode(q, k, v, valid, impl: str | None = None):
    impl = impl or kernel_impl()
    if impl == "ref":
        return ref.attention_decode(q, k, v, valid)
    from .decode_attention import decode_attention
    return decode_attention(q, k, v, valid, interpret=(impl == "interpret"))


def ring_gather(hist, idx, impl: str | None = None):
    """Gather one stacked version: hist (size, R, 128), idx scalar ->
    hist[idx] (R, 128) — the packed ParameterDB ring row."""
    impl = impl or kernel_impl()
    if impl == "ref":
        return ref.ring_gather(hist, idx)
    from .ring_gather import ring_gather as _rg
    return _rg(hist, idx, interpret=(impl == "interpret"))


def page_gather(pool, page_table, impl: str | None = None):
    """Paged-KV logical view: pool (P, page, ...) + page_table (B, n_pp)
    -> (B, n_pp * page, ...) — the serving engine's cache materializer."""
    impl = impl or kernel_impl()
    if impl == "ref":
        return ref.page_gather(pool, page_table)
    from .page_gather import page_gather as _pg
    return _pg(pool, page_table, interpret=(impl == "interpret"))


def prefill_page_attention(q, k_ctx, v_ctx, k_new, v_new, ctx_pos, q_pos,
                           window: int = 0, impl: str | None = None):
    """Chunked-prefill attention: chunk queries (B, C, H, hd) against the
    gathered paged context (B, L, KV, hd) plus in-chunk keys, masked by
    absolute positions (ctx_pos/q_pos; negative = dead slot)."""
    impl = impl or kernel_impl()
    if impl == "ref":
        return ref.prefill_page_attention(q, k_ctx, v_ctx, k_new, v_new,
                                          ctx_pos, q_pos, window=window)
    from .page_gather import prefill_page_attention as _ppa
    return _ppa(q, k_ctx, v_ctx, k_new, v_new, ctx_pos, q_pos,
                window=window, interpret=(impl == "interpret"))


def moe_grouped_ffn(dispatch, combine, xg, wg, wu, wd, ep=None,
                    impl: str | None = None):
    """Grouped-expert FFN over dispatched token groups (models/moe.py).

    The ``ep`` sharding hook only applies on the XLA path — the Pallas
    kernel never materializes the dispatched (E, G, C, d) intermediate it
    would constrain.
    """
    impl = impl or kernel_impl()
    if impl == "ref":
        return ref.moe_grouped_ffn(dispatch, combine, xg, wg, wu, wd, ep=ep)
    from .moe_matmul import moe_grouped_ffn as _moe
    return _moe(dispatch, combine, xg, wg, wu, wd,
                interpret=(impl == "interpret"))


def rwkv6(r, k, v, w, u, impl: str | None = None):
    impl = impl or kernel_impl()
    if impl == "ref":
        return ref.rwkv6(r, k, v, w, u)
    from .rwkv6_scan import rwkv6_scan
    return rwkv6_scan(r, k, v, w, u, interpret=(impl == "interpret"))


def rwkv6_stateful(r, k, v, w, u, S0, impl: str | None = None):
    # Stateful (decode) path: T is tiny; the scan reference is optimal.
    return ref.rwkv6_stateful(r, k, v, w, u, S0)


def rglru(x, a, impl: str | None = None):
    impl = impl or kernel_impl()
    if impl == "ref":
        h, _ = ref.rglru(x, a)
        return h
    from .rglru_scan import rglru_scan
    return rglru_scan(x, a, interpret=(impl == "interpret"))


def rglru_stateful(x, a, h0, impl: str | None = None):
    return ref.rglru(x, a, h0)
