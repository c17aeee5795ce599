"""The main-path Pallas kernels compile for a TPU v5e at SmolLM-360M widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a v5e chip that is described, not attached, which catches what interpret
mode cannot (block shapes the tiling refuses, VMEM overflow).  The
topology is described inside a fixture, so collecting this file loads no
TPU library.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_gather import page_gather, prefill_page_attention
from repro.kernels.ring_gather import ring_gather

# SmolLM-360M (configs/smollm_360m.py): 15 query heads, 5 KV heads, hd 64
H, KV, HD = 15, 5, 64
N_PARAMS = 361_821_120           # f32 master copy packed into one ring row
PAGE = 16
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _decode(B, L, dt):
    return (lambda q, k, v, m: decode_attention(q, k, v, m),
            [((B, 1, H, HD), dt), ((B, L, KV, HD), dt), ((B, L, KV, HD), dt),
             ((B, L), jnp.bool_)])


def _prefill(B, L, C, dt):
    return (lambda q, kc, vc, kn, vn, cp, qp: prefill_page_attention(
                q, kc, vc, kn, vn, cp, qp),
            [((B, C, H, HD), dt), ((B, L, KV, HD), dt), ((B, L, KV, HD), dt),
             ((B, C, KV, HD), dt), ((B, C, KV, HD), dt), ((B, L), i32),
             ((B, C), i32)])


def _flash(B, S, nh, nkv, hd, dt, window=0):
    """At the blocks the wrapper chooses for this shape."""
    return (lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=window),
            [((B, S, nh, hd), dt), ((B, S, nkv, hd), dt),
             ((B, S, nkv, hd), dt)])


CASES = {
    "decode-bf16-b8-l512": _decode(8, 512, bf16),
    "decode-f32-b4-l512": _decode(4, 512, f32),
    "decode-bf16-b8-l1000": _decode(8, 1000, bf16),       # padded cache tail
    "ring-f32-delta2-smollm": (
        lambda h, i: ring_gather(h, i),
        [((3, math.ceil(N_PARAMS / 1024) * 8, 128), f32), ((), i32)]),
    "page-bf16-b8": (
        lambda p, t: page_gather(p, t),
        [((8 * 32 + 1, PAGE, KV, HD), bf16), ((8, 32), i32)]),
    "prefill-bf16-b1-c128": _prefill(1, 512, 128, bf16),
    "prefill-bf16-b2-c128": _prefill(2, 512, 128, bf16),
    "flash-bf16-b8-s1024": _flash(8, 1024, H, KV, HD, bf16),
    "flash-bf16-b2-s1024-window256": _flash(2, 1024, H, KV, HD, bf16,
                                            window=256),
    "flash-f32-b8-s1024": _flash(8, 1024, H, KV, HD, f32),
    "flash-bf16-b2-s2048-hd128": _flash(2, 2048, 32, 8, 128, bf16),
    "flash-f32-b2-s1024-hd256": _flash(2, 1024, 8, 4, 256, f32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
