"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import (VMEM_BUDGET, choose_blocks,
                                           flash_attention, vmem_bytes)
from repro.kernels.moe_matmul import moe_grouped_ffn
from repro.kernels.page_gather import page_gather
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ring_gather import ring_gather
from repro.kernels.rwkv6_scan import rwkv6_scan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("S,H,KV,hd,bq,bk", [
        (64, 4, 4, 32, 32, 32),      # MHA
        (96, 4, 2, 32, 32, 32),      # GQA, ragged block tail
        (128, 6, 2, 16, 64, 32),     # GQA 3:1, mixed blocks
        (33, 2, 1, 8, 16, 16),       # non-multiple seq (padding path)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 24),
                                               (False, 0)])
    def test_matches_ref(self, S, H, KV, hd, bq, bk, dtype, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (2, S, H, hd), dtype)
        k = _rand(ks[1], (2, S, KV, hd), dtype)
        v = _rand(ks[2], (2, S, KV, hd), dtype)
        want = ref.attention(q, k, v, causal=causal, window=window)
        got = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    @pytest.mark.parametrize("S,H,KV,causal,window", [
        (256, 6, 2, True, 0),        # GQA 3:1
        (256, 6, 2, True, 64),       # GQA 3:1, window narrower than S
        (256, 6, 2, False, 0),       # GQA 3:1, non-causal
        (256, 4, 4, True, 0),        # MHA
        (200, 6, 2, True, 0),        # ragged S (padding path)
        (1024, 3, 1, True, 0),       # several q and kv blocks: masked steps
        (1100, 3, 1, True, 300),     # ragged, windowed, several blocks
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_chosen_blocks_match_ref(self, S, H, KV, causal, window, dtype):
        """Blocks left to the chooser, at hd 64."""
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = _rand(ks[0], (1, S, H, 64), dtype)
        k = _rand(ks[1], (1, S, KV, 64), dtype)
        v = _rand(ks[2], (1, S, KV, 64), dtype)
        want = ref.attention(q, k, v, causal=causal, window=window)
        got = flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    @pytest.mark.parametrize("S", [33, 1024, 4096])
    @pytest.mark.parametrize("hd", [64, 128, 256])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("group,causal,window", [
        (3, True, 0), (1, True, 0), (4, False, 0), (3, True, 256)])
    def test_block_chooser(self, S, hd, dtype, group, causal, window):
        """(8, 128)-legal blocks that tile the padded sequence and fit the
        VMEM budget the chooser assumes."""
        bq, bk = choose_blocks(S, hd, group, dtype, causal, window)
        s_pad = -(-S // 128) * 128
        assert bq % 8 == 0 and bk % 128 == 0
        assert bq % bk == 0 and s_pad % bq == 0
        if window:
            assert bk <= max(128, window)
        assert vmem_bytes(group, bq, bk, hd, jnp.dtype(dtype).itemsize) \
            <= VMEM_BUDGET

    def test_grad_matches_ref(self):
        """The dispatch's Pallas forward is differentiable (training runs
        it): its gradient is the reference's."""
        from repro.kernels import ops
        ks = jax.random.split(jax.random.PRNGKey(4), 4)
        q = _rand(ks[0], (2, 48, 4, 16), jnp.float32)
        k = _rand(ks[1], (2, 48, 2, 16), jnp.float32)
        v = _rand(ks[2], (2, 48, 2, 16), jnp.float32)
        w = _rand(ks[3], (2, 48, 4, 16), jnp.float32)

        def loss(impl):
            return lambda q, k, v: jnp.sum(
                ops.attention(q, k, v, window=24, impl=impl) * w)
        got = jax.grad(loss("interpret"), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)

    def test_chunked_xla_path_matches(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(ks[0], (1, 4096, 2, 16), jnp.float32)
        k = _rand(ks[1], (1, 4096, 1, 16), jnp.float32)
        v = _rand(ks[2], (1, 4096, 1, 16), jnp.float32)
        want = ref.attention(q, k, v, causal=True, window=128)
        got = ref.attention_chunked(q, k, v, causal=True, window=128,
                                    block_q=512)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestRWKV6:
    @pytest.mark.parametrize("T,H,hd,bt", [
        (32, 2, 16, 16), (48, 4, 32, 16), (40, 1, 64, 32),  # ragged tail
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, T, H, hd, bt, dtype):
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        B = 2
        r = _rand(ks[0], (B, T, H, hd), dtype) * 0.5
        k = _rand(ks[1], (B, T, H, hd), dtype) * 0.5
        v = _rand(ks[2], (B, T, H, hd), dtype) * 0.5
        w = (jax.nn.sigmoid(_rand(ks[3], (B, T, H, hd), jnp.float32))
             * 0.5 + 0.45).astype(dtype)
        u = _rand(ks[4], (H, hd), dtype) * 0.1
        want = ref.rwkv6(r, k, v, w, u)
        got = rwkv6_scan(r, k, v, w, u, block_t=bt, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_stateful_continuation(self):
        """Splitting a sequence across two stateful calls == one call."""
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        B, T, H, hd = 1, 24, 2, 16
        r = _rand(ks[0], (B, T, H, hd), jnp.float32) * 0.5
        k = _rand(ks[1], (B, T, H, hd), jnp.float32) * 0.5
        v = _rand(ks[2], (B, T, H, hd), jnp.float32) * 0.5
        w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, hd), jnp.float32)) * 0.5 + 0.4
        u = _rand(ks[4], (H, hd), jnp.float32) * 0.1
        full = ref.rwkv6(r, k, v, w, u)
        S0 = jnp.zeros((B, H, hd, hd))
        y1, S1 = ref.rwkv6_stateful(r[:, :10], k[:, :10], v[:, :10],
                                    w[:, :10], u, S0)
        y2, _ = ref.rwkv6_stateful(r[:, 10:], k[:, 10:], v[:, 10:],
                                   w[:, 10:], u, S1)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), full,
                                   rtol=1e-5, atol=1e-6)


class TestRGLRU:
    @pytest.mark.parametrize("T,D,bd,bt", [
        (32, 64, 64, 16), (48, 160, 64, 32), (50, 96, 32, 16),  # ragged
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, T, D, bd, bt, dtype):
        ks = jax.random.split(jax.random.PRNGKey(4), 2)
        x = _rand(ks[0], (2, T, D), dtype)
        a = jax.nn.sigmoid(_rand(ks[1], (2, T, D), jnp.float32)).astype(dtype)
        want, _ = ref.rglru(x, a)
        got = rglru_scan(x, a, block_d=bd, block_t=bt, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_stateful_continuation(self):
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        x = _rand(ks[0], (1, 20, 32), jnp.float32)
        a = jax.nn.sigmoid(_rand(ks[1], (1, 20, 32), jnp.float32))
        full, hT = ref.rglru(x, a)
        y1, h1 = ref.rglru(x[:, :7], a[:, :7])
        y2, h2 = ref.rglru(x[:, 7:], a[:, 7:], h0=h1)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), full,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(h2, hT, rtol=1e-5, atol=1e-6)


class TestDecodeAttentionFused:
    """Fused single-token GQA decode kernel vs ref.attention_decode."""

    @pytest.mark.parametrize("B,L,H,KV,hd,bk", [
        (2, 32, 4, 4, 16, 16),     # MHA
        (2, 40, 8, 2, 16, 16),     # GQA 4:1, ragged kv tail
        (1, 64, 6, 2, 32, 32),     # GQA 3:1
        (3, 17, 4, 1, 8, 8),       # MQA, non-multiple cache len
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, L, H, KV, hd, bk, dtype):
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        q = _rand(ks[0], (B, 1, H, hd), dtype)
        k = _rand(ks[1], (B, L, KV, hd), dtype)
        v = _rand(ks[2], (B, L, KV, hd), dtype)
        # ring-style liveness: a hole plus a dead tail, as produced by the
        # slot = pos % L convention mid-generation
        valid = (jnp.arange(L) % 5 != 3) & (jnp.arange(L) < L - 2)
        want = ref.attention_decode(q, k, v, valid)
        got = decode_attention(q, k, v, valid, block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_per_sequence_valid_rows(self):
        """(B, L) valid — continuous batching puts every sequence at its
        own position, so each batch row carries its own liveness mask."""
        ks = jax.random.split(jax.random.PRNGKey(15), 3)
        B, L, H, KV, hd = 3, 32, 4, 2, 16
        q = _rand(ks[0], (B, 1, H, hd), jnp.float32)
        k = _rand(ks[1], (B, L, KV, hd), jnp.float32)
        v = _rand(ks[2], (B, L, KV, hd), jnp.float32)
        # ring masks for pos = 0, 13, 45 (slot = pos % L, wrap-around row)
        pos = jnp.asarray([0, 13, 45])[:, None]
        idx = jnp.arange(L)[None, :]
        abs_pos = pos - jnp.mod(pos - idx, L)
        valid = (abs_pos >= 0) & (abs_pos >= pos - (L - 1))
        assert valid.shape == (B, L) and int(valid[0].sum()) == 1
        want = ref.attention_decode(q, k, v, valid)
        got = decode_attention(q, k, v, valid, block_k=16, interpret=True)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_single_live_slot(self):
        """pos=0: only slot 0 valid — blocks past it are fully dead and
        must not pollute the online softmax."""
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        B, L, H, KV, hd = 2, 48, 4, 2, 16
        q = _rand(ks[0], (B, 1, H, hd), jnp.float32)
        k = _rand(ks[1], (B, L, KV, hd), jnp.float32)
        v = _rand(ks[2], (B, L, KV, hd), jnp.float32)
        valid = jnp.arange(L) == 0
        want = ref.attention_decode(q, k, v, valid)
        got = decode_attention(q, k, v, valid, block_k=16, interpret=True)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # only v[:, 0] should survive the softmax
        np.testing.assert_allclose(
            got[:, 0], ref._repeat_kv(v, H)[:, 0], rtol=2e-5, atol=2e-5)


class TestRingGatherKernel:
    """Scalar-prefetch row DMA vs hist[idx] — must be bit-identical."""

    @pytest.mark.parametrize("size,R,C", [
        (1, 1, 128),               # delta=0 degenerate ring
        (4, 8, 128),               # whole (8, 128) tiles
        (4, 5, 200),               # ragged rows and lanes
        (3, 1, 64),                # one partial tile
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_bit_identical(self, size, R, C, dtype):
        hist = _rand(jax.random.PRNGKey(12), (size, R, C), dtype)
        for i in range(size):
            got = ring_gather(hist, jnp.asarray(i, jnp.int32),
                              interpret=True)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(hist[i]))

    def test_matches_ref_dispatch(self):
        hist = _rand(jax.random.PRNGKey(13), (5, 3, 128), jnp.float32)
        idx = jnp.asarray(3, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(ring_gather(hist, idx, interpret=True)),
            np.asarray(ref.ring_gather(hist, idx)))


class TestPageGatherKernel:
    """Scalar-prefetch page gather vs pool[page_table] — bit-identical."""

    @pytest.mark.parametrize("P,page,KV,hd,B,npp", [
        (9, 8, 2, 16, 2, 4),
        (5, 4, 1, 8, 2, 2),            # MQA, short pages
        (7, 8, 5, 64, 3, 2),           # SmolLM head shape (GQA kv=5)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_bit_identical(self, P, page, KV, hd, B, npp, dtype):
        ks = jax.random.split(jax.random.PRNGKey(16), 2)
        pool = _rand(ks[0], (P, page, KV, hd), dtype)
        pt = jax.random.randint(ks[1], (B, npp), 0, P).astype(jnp.int32)
        got = page_gather(pool, pt, interpret=True)
        want = ref.page_gather(pool, pt)
        assert got.shape == (B, npp * page, KV, hd)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_shared_and_junk_pages(self):
        """Two sequences may map the same physical page (and idle slots
        all map the junk page) — the gather must not care."""
        pool = _rand(jax.random.PRNGKey(17), (4, 4, 1, 8), jnp.float32)
        pt = jnp.asarray([[2, 2], [3, 3]], jnp.int32)
        got = page_gather(pool, pt, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.page_gather(pool, pt)))


def _routing(key, G, g, E, C, k=2):
    """Top-k dispatch/combine tensors the way models/moe.py builds them."""
    probs = jax.nn.softmax(jax.random.normal(key, (G, g, E)))
    remaining = probs
    combine = jnp.zeros((G, g, E, C), jnp.float32)
    dispatch = jnp.zeros((G, g, E, C), bool)
    fill = jnp.zeros((G, E), jnp.int32)
    for _ in range(k):
        gate, idx = jax.lax.top_k(remaining, 1)
        gate, idx = gate[..., 0], idx[..., 0]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        pos = fill[:, None, :] + (jnp.cumsum(onehot, axis=1)
                                  - onehot).astype(jnp.int32)
        keep = onehot.astype(bool) & (pos < C)
        slot = jax.nn.one_hot(jnp.where(keep, pos, C), C,
                              dtype=jnp.float32) * keep[..., None]
        dispatch |= slot.astype(bool)
        combine = combine + slot * gate[..., None, None]
        fill = fill + jnp.sum(onehot, axis=1).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    return dispatch, combine


class TestMoEGroupedKernel:
    """Grouped per-expert contraction vs the one-hot EGCd einsum path."""

    @pytest.mark.parametrize("G,g,E,C,d,f", [
        (1, 16, 4, 8, 32, 48),
        (2, 24, 4, 16, 16, 64),
        (1, 32, 8, 10, 64, 96),    # capacity drops (over-capacity tokens)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, G, g, E, C, d, f, dtype):
        ks = jax.random.split(jax.random.PRNGKey(14), 5)
        dispatch, combine = _routing(ks[0], G, g, E, C)
        xg = _rand(ks[1], (G, g, d), dtype)
        wg = _rand(ks[2], (E, d, f), dtype) * 0.1
        wu = _rand(ks[3], (E, d, f), dtype) * 0.1
        wd = _rand(ks[4], (E, f, d), dtype) * 0.1
        want = ref.moe_grouped_ffn(dispatch, combine, xg, wg, wu, wd)
        got = moe_grouped_ffn(dispatch, combine, xg, wg, wu, wd,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_moe_ffn_end_to_end(self, monkeypatch):
        """Full moe_ffn (router + capacity + aux) under both impls."""
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.models import paramlib
        from repro.models.moe import moe_ffn, moe_specs
        cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"),
                                  dtype=jnp.float32, capacity_factor=4.0)
        params = paramlib.init_tree(moe_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                              jnp.float32)
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
        want, aux_want = moe_ffn(params, x, cfg)
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
        got, aux_got = moe_ffn(params, x, cfg)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux_got["lb_loss"]),
                                   float(aux_want["lb_loss"]), rtol=1e-6)


class TestDecode:
    def test_attention_decode_matches_full(self):
        """Decode against a cache == last row of full attention."""
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        B, S, H, KV, hd = 2, 17, 4, 2, 16
        q = _rand(ks[0], (B, S, H, hd), jnp.float32)
        k = _rand(ks[1], (B, S, KV, hd), jnp.float32)
        v = _rand(ks[2], (B, S, KV, hd), jnp.float32)
        full = ref.attention(q, k, v, causal=True)
        got = ref.attention_decode(q[:, -1:], k, v,
                                   jnp.ones((S,), bool))
        np.testing.assert_allclose(got[:, 0], full[:, -1],
                                   rtol=1e-5, atol=1e-6)


class TestPrefillPageAttention:
    """Chunked-prefill attention (context ring + in-chunk causal) vs the
    XLA reference, and both vs dense full-sequence attention."""

    @pytest.mark.parametrize("L,C,H,KV,hd,window,bk", [
        (32, 8, 4, 2, 16, 0, 16),     # GQA, full attn
        (16, 8, 2, 2, 8, 16, 8),      # MHA, windowed ring
        (24, 6, 4, 1, 16, 0, 128),    # ragged: one padded k block
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, L, C, H, KV, hd, window, bk, dtype):
        from repro.kernels.page_gather import prefill_page_attention
        ks = jax.random.split(jax.random.PRNGKey(21), 5)
        B, start = 2, 10
        q = _rand(ks[0], (B, C, H, hd), dtype)
        k_ctx = _rand(ks[1], (B, L, KV, hd), dtype)
        v_ctx = _rand(ks[2], (B, L, KV, hd), dtype)
        k_new = _rand(ks[3], (B, C, KV, hd), dtype)
        v_new = _rand(ks[4], (B, C, KV, hd), dtype)
        idx = jnp.arange(L, dtype=jnp.int32)
        last = start - 1
        abs_pos = last - jnp.mod(last - idx, L)      # ring reconstruction
        ctx_pos = jnp.broadcast_to(
            jnp.where(abs_pos >= 0, abs_pos, -1)[None], (B, L))
        q_pos = jnp.broadcast_to(
            (start + jnp.arange(C, dtype=jnp.int32))[None], (B, C))
        want = ref.prefill_page_attention(q, k_ctx, v_ctx, k_new, v_new,
                                          ctx_pos, q_pos, window=window)
        got = prefill_page_attention(q, k_ctx, v_ctx, k_new, v_new,
                                     ctx_pos, q_pos, window=window,
                                     block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_matches_dense_attention(self):
        """Context slots 0..start-1 + chunk == rows start..start+C-1 of
        one dense causal attention over the whole sequence."""
        ks = jax.random.split(jax.random.PRNGKey(22), 3)
        B, S, start, H, KV, hd = 1, 24, 16, 4, 2, 16
        C, L = S - start, 32
        q = _rand(ks[0], (B, S, H, hd), jnp.float32)
        k = _rand(ks[1], (B, S, KV, hd), jnp.float32)
        v = _rand(ks[2], (B, S, KV, hd), jnp.float32)
        full = ref.attention(q, k, v, causal=True)
        k_ctx = jnp.zeros((B, L, KV, hd)).at[:, :start].set(k[:, :start])
        v_ctx = jnp.zeros((B, L, KV, hd)).at[:, :start].set(v[:, :start])
        ctx_pos = jnp.where(jnp.arange(L) < start, jnp.arange(L), -1)[None]
        q_pos = (start + jnp.arange(C))[None].astype(jnp.int32)
        got = ref.prefill_page_attention(
            q[:, start:], k_ctx, v_ctx, k[:, start:], v[:, start:],
            ctx_pos.astype(jnp.int32), q_pos)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(full[:, start:]),
                                   rtol=1e-5, atol=1e-5)
