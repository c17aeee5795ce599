"""Operation and byte counts against values worked by hand at smoke size;
the table of peaks; the traffic generator."""
from __future__ import annotations

import numpy as np
import pytest

from bench import counts, generator, harness
from conftest import SMOKE_TRAFFIC

# d 64, 4 heads of 16, 2 KV heads, MLP 128, vocab 512, 2 layers, bf16
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512}
CONFIG = {**harness.resolve("smollm360m.train.ring_d2").config, **SMALL}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def test_matmul_params_and_train_flops():
    # per layer 64*64 (q) + 2*64*32 (k, v) + 64*64 (o) + 3*64*128 (MLP)
    layer = 4096 + 4096 + 4096 + 24576
    assert counts.matmul_params(CONFIG) == 2 * layer + 512 * 64 == 106_496
    # 6 N + 3 x 4 H hd L (S/2): 638,976 + 98,304
    assert counts.train_flops_per_token(CONFIG, 128) == 737_280
    assert counts.weight_bytes(CONFIG) == 2 * 106_496


def test_train_mfu_reader():
    ctx = {"kind": "train", "config": CONFIG, "traffic": {"seq": 128},
           "tokens_per_step": 512, "traced": {"steps": 2},
           "trace_window": (0.0, 1e9), "chips": 1, "peaks": PEAKS}
    mfu = harness.metric_reader("train.mfu").read(ctx)
    assert mfu == pytest.approx(100 * 737_280 * 512 * 2 / 1e12)
    assert harness.metric_reader("train.mfu").read(
        {**ctx, "kind": "serve"}) is None


def test_serve_tick_counts():
    mod = harness.metric_reader("serve_chat.mfu")
    flops, bytes_ = mod.tick_work(CONFIG, live=3, kv_positions=100)
    # 2 N x 3 sequences + 4 H hd L x 100 positions
    assert flops == 2 * 106_496 * 3 + 4 * 4 * 16 * 2 * 100 == 690_176
    # bf16 weights + K and V of 100 positions: 2 x 2 heads x 16 x 2 B x 2 L
    assert bytes_ == 212_992 + 25_600
    flops, bytes_ = mod.chunk_work(CONFIG, 32)
    assert flops == 2 * 106_496 * 32 + 4 * 4 * 16 * 2 * (32 * 33 / 2)
    assert bytes_ == 212_992


def test_serve_mfu_reader_over_ticks():
    mod = harness.metric_reader("serve_chat.mfu")
    # (time, live after the step, sum of their positions, chunks so far)
    ticks = [(0.0, 3, 97, 0), (0.5, 3, 100, 1), (1.0, 2, 60, 1),
             (5.0, 1, 10, 1), (6.0, 0, 0, 1)]
    ctx = {"kind": "serve", "ticks": ticks, "host_window": (0.1, 1.1),
           "config": CONFIG, "peaks": PEAKS,
           "traffic": {"engine": {"prefill_chunk": 32}}}
    least = (counts.roofline_s(*mod.tick_work(CONFIG, 3, 100), PEAKS)
             + counts.roofline_s(*mod.chunk_work(CONFIG, 32), PEAKS)
             + counts.roofline_s(*mod.tick_work(CONFIG, 3, 103), PEAKS))
    assert mod.read(ctx) == pytest.approx(100 * least / 1.0)
    tick = harness.metric_reader("serve_chat.tick_ms")
    # only the steps wholly outside the traced part count
    assert tick.read(ctx) == pytest.approx(1000.0)


def test_kernel_counts():
    flops, bytes_ = counts.attn_call(CONFIG, batch=4, seq=128)
    assert flops == 4 * 4 * 4 * 16 * 128 * 128 / 2 == 8_388_608
    assert bytes_ == 4 * 128 * 16 * (2 * 4 + 2 * 2) * 2 == 196_608
    ring = harness.metric_reader("ring_gather_roofline")
    # 106,496 + 2 x 2 x 64 + 64 norm gains = 106,816, padded to 107,520
    assert ring.call_bytes(CONFIG) == 2 * 107_520 * 4
    assert counts.kv_bytes(CONFIG, 10) == 2 * 2 * 16 * 2 * 2 * 10
    assert counts.roofline_s(2e12, 1e9, PEAKS) == 2.0


def test_roofline_reader_on_trace():
    from bench import trace
    E = trace.Event
    tr = trace.Trace(devices={"d": [E("flash_attention.18", 0, 400_000),
                                    E("flash_attention.19", 500_000, 400_000),
                                    E("fusion.2", 400_000, 100_000),
                                    E("fusion.3", 450_000, 10_000)]},
                     host=[])
    ctx = {"kind": "train", "trace": tr, "trace_window": (0, 1e6),
           "devices": ["d"], "config": CONFIG, "peaks": PEAKS,
           "traffic": {"batch": 4, "seq": 128}}
    flash = harness.metric_reader("flash_attention_roofline")
    least = counts.roofline_s(*counts.attn_call(CONFIG, 4, 128), PEAKS)
    assert flash.read(ctx) == pytest.approx(100 * 2 * least / 8e-4)
    assert harness.metric_reader("ring_gather_roofline").read(ctx) is None


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# Traffic
# ---------------------------------------------------------------------------

SERVE = {**harness.resolve("smollm360m.serve.chat").traffic}


def _lengths(reqs):
    return [len(r.prompt) for r in reqs], [r.gen_len for r in reqs]


def test_serve_traffic_is_seeded_and_same_work_per_seed():
    a = generator.serve_requests(SERVE, CONFIG, 2 ** 33 + 5, 30.0)
    b = generator.serve_requests(SERVE, CONFIG, 2 ** 33 + 5, 30.0)
    c = generator.serve_requests(SERVE, CONFIG, 17, 30.0)
    assert a == b
    assert a != c
    assert sorted(zip(*_lengths(a))) == sorted(zip(*_lengths(c)))
    assert sorted(r.arrival for r in a) != [r.arrival for r in c]
    assert len(a) == round(SERVE["rate"] * 30)
    assert a[0].arrival == 0.0 and a[-1].arrival < 30.0


def test_serve_traffic_respects_limits_and_median():
    reqs = generator.serve_requests(SERVE, CONFIG, 3, 30.0)
    p, o = _lengths(reqs)
    assert min(p) >= SERVE["prompt"]["min"] and max(p) <= SERVE["prompt"]["max"]
    assert min(o) >= SERVE["output"]["min"] and max(o) <= SERVE["output"]["max"]
    assert max(a + b for a, b in zip(p, o)) <= SERVE["engine"]["cache_len"]
    assert np.median(p) == pytest.approx(SERVE["prompt"]["median"], abs=2)
    assert np.median(o) == pytest.approx(SERVE["output"]["median"], abs=2)
    assert all(0 <= t < CONFIG["vocab_size"] for r in reqs for t in r.prompt)
    gaps = np.diff([r.arrival for r in reqs])
    assert np.median(gaps) == pytest.approx(np.log(2) / SERVE["rate"],
                                            rel=0.05)


def test_serve_traffic_refuses_a_request_past_cache_len():
    small = {**SERVE, "engine": {**SERVE["engine"], "cache_len": 64}}
    with pytest.raises(harness.BenchError):
        generator.serve_requests(small, CONFIG, 1, 5.0)


def test_train_batches_are_seeded_and_rows_differ():
    t = {**SMOKE_TRAFFIC["train"]}
    a_tok, a_lab = generator.train_batch(t, CONFIG, 2 ** 40 + 1, 0)
    b_tok, _ = generator.train_batch(t, CONFIG, 2 ** 40 + 1, 0)
    c_tok, _ = generator.train_batch(t, CONFIG, 2 ** 40 + 1, 1)
    assert (np.asarray(a_tok) == np.asarray(b_tok)).all()
    assert not (np.asarray(a_tok) == np.asarray(c_tok)).all()
    assert (np.asarray(a_tok)[:, 1:] == np.asarray(a_lab)[:, :-1]).all()
    rows = {tuple(r) for r in np.asarray(a_tok)}
    assert len(rows) == t["batch"]
    assert a_tok.shape == (t["batch"], t["seq"])
