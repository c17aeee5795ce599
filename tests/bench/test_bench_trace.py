"""The trace reduction on a small recorded trace, and the benchmark's files
found by the names in BENCHMARK.json."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import harness, trace

DATA = Path(__file__).parent / "data" / "small_trace.json"
WIN = (0.0, 10_000.0)


@pytest.fixture(scope="module")
def tr():
    return trace.load_json(DATA)


def test_busy_idle_union(tr):
    d0, d1 = tr.devices["/device:TPU:0"], tr.devices["/device:TPU:1"]
    assert trace.union(d0, *WIN) == [(1000, 4000), (5000, 7000),
                                     (8000, 9000), (9500, 10000)]
    assert trace.busy_ns(d0, *WIN) == 6500
    assert trace.busy_ns(d1, *WIN) == 9000
    assert trace.gaps(d0, *WIN) == [(0, 1000), (4000, 5000), (7000, 8000),
                                    (9000, 9500)]


def test_kernel_time_by_name(tr):
    d0 = tr.devices["/device:TPU:0"]
    assert trace.op_ns(d0, r"_attn_kernel", *WIN) == (1500, 1)
    assert trace.op_ns(d0, r"_gather_kernel", *WIN) == (1000, 1)
    assert trace.op_ns(d0, r"^fusion\.1$", *WIN) == (2000, 2)   # clipped
    # by self time: fusion.2 runs inside the all-gather's interval
    assert dict(trace.top_ops(d0, *WIN)) == pytest.approx({
        "fusion.1": 2e-6, "all-gather.3": 1.5e-6, "_attn_kernel": 1.5e-6,
        "_gather_kernel": 1e-6, "fusion.2": 5e-7})


def test_op_names_and_self_time():
    assert trace.op_name("%flash_attention.18 = bf16[8,15,1024,64] "
                         "custom-call(%ring_gather.1)") == "flash_attention.18"
    E = trace.Event
    loop = [E("while.1", 0, 100), E("a", 10, 20), E("b", 40, 30),
            E("c", 120, 10)]
    assert sorted(trace.self_times(loop, 0, 200)) == [
        ("a", 20), ("b", 30), ("c", 10), ("while.1", 50)]
    assert trace.top_ops(loop, 0, 200)[0] == ["while.1", pytest.approx(5e-8)]


def test_exposed_collectives(tr):
    assert trace.exposed_ns(tr.devices["/device:TPU:0"], *WIN) == 1500
    assert trace.exposed_ns(tr.devices["/device:TPU:1"], *WIN) == 3000


def test_gap_attribution(tr):
    got = dict(trace.attribute_gaps(tr.devices["/device:TPU:0"], tr.host,
                                    *WIN))
    want = {"bench.step": 1.7e-6, "bench.host": 7e-7, "bench.batch": 6e-7,
            "bench.window": 5e-7}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])
    assert sum(got.values()) == pytest.approx(3.5e-6)   # all idle time


def test_window_span_and_round_trip(tr, tmp_path):
    assert tr.span("bench.window") == (0, 10000)
    trace.save_json(tr, tmp_path / "t.json")
    assert trace.load_json(tmp_path / "t.json") == tr


def test_load_reads_benchmark_host_spans(tmp_path):
    """An ``.xplane.pb`` from this host: the benchmark's spans are found,
    and a host without an accelerator has no device operations."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    got = trace.load(tmp_path)
    lo, hi = got.span("bench.window")
    assert hi > lo
    assert got.devices == {}


def test_chip_trace_excerpt():
    """40 ms of a train step's trace from a TPU v5e: the kernels' op names
    match the readers' patterns, operations nest (self times add up to the
    busy time), and the ring read reads under 100 % of its roofline."""
    chip = trace.load_json(DATA.parent / "chip_train_excerpt.json")
    (name, ops), = chip.devices.items()
    lo, hi = min(e.start for e in ops), max(e.end for e in ops)
    assert all(" = " not in e.name for e in ops)
    assert sum(t for _, t in trace.self_times(ops, lo, hi)) == \
        pytest.approx(trace.busy_ns(ops, lo, hi))
    cell = harness.resolve("smollm360m.train.ring_d2")
    ctx = {"kind": "train", "trace": chip, "trace_window": (lo, hi),
           "devices": [name], "config": cell.config, "traffic": cell.traffic,
           "peaks": harness.peaks("TPU v5 lite")}
    ring = harness.metric_reader("ring_gather_roofline")
    flash = harness.metric_reader("flash_attention_roofline")
    assert trace.op_ns(ops, ring.PATTERN, lo, hi)[1] == 1
    assert trace.op_ns(ops, flash.PATTERN, lo, hi)[1] == 4
    assert 50 < ring.read(ctx) <= 100
    assert 0 < flash.read(ctx) <= 100


def test_device_idle_reader(tr):
    ctx = {"kind": "train", "trace": tr, "trace_window": WIN,
           "devices": sorted(tr.devices)}
    idle = harness.metric_reader("train.device_idle").read(ctx)
    assert idle == pytest.approx(100 * (1 - (6500 + 9000) / 2 / 10000))


# ---------------------------------------------------------------------------
# Everything found by name
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_benchmark_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for p in bench["paths"]:
        assert (harness.ROOT / p).is_dir()
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all("\n" not in x for x in layers)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.benchmark()["workloads"]])
def test_cell_resolves_by_name(cell, bench):
    c = harness.resolve(cell, bench)
    assert c.config["name"] == next(
        w["config"] for w in bench["workloads"] if w["name"] == cell)
    assert callable(c.driver.run)
    assert c.limits and all("limit" in v for v in c.limits.values())
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]).read)


def test_every_config_and_metric_is_used(bench):
    cells = [harness.resolve(w["name"], bench) for w in bench["workloads"]]
    assert {c["name"] for c in bench["configs"]} == {
        w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        f = json.loads((harness.ROOT / c["file"]).read_text())
        assert f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
    reported = {m["name"] for c in cells for m in c.per_layer}
    assert reported == {m["name"] for m in bench["per_layer"]}


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.resolve("no.such.cell")
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric")
