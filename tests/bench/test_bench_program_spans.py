"""The program's own spans (``repro.*``): the reader of the serving tick's
host time on a small hand-made trace, the trace reduction's attribution of
idle gaps to them, the existing readers unmoved by them, and a traced CPU
rehearsal of the serve driver in which they are found."""
from __future__ import annotations

import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness, program_spans, trace

DATA = Path(__file__).parent / "data"
WIN = (0.0, 10_000.0)
SERVE = "smollm360m.serve.chat"
SEED = 2 ** 33 + 17
NEW = {"serve_chat.tick_host_ms"}        # readers of the program's spans


@pytest.fixture(scope="module")
def tr():
    return trace.load_json(DATA / "program_spans_trace.json")


def _program(events):
    return [e for e in events if e.name.startswith(program_spans.PREFIX)]


def _without_program(t):
    return trace.Trace(devices=t.devices, host=[
        e for e in t.host if not e.name.startswith(program_spans.PREFIX)])


def test_tick_host_ms_by_hand(tr):
    """Ticks 500-3500 (readback 1000) and 4000-8000 (readback 2500): 2000
    and 1500 ns of host work; the tick 8500-10500 leaves the window."""
    reader = harness.metric_reader("serve_chat.tick_host_ms")
    tick_host_ms = reader.tick_host_ms
    spans = _program(tr.host)
    assert tick_host_ms(spans, *WIN) == pytest.approx(1750e-6)
    assert tick_host_ms(spans, 0, 4000) == pytest.approx(2000e-6)
    assert tick_host_ms(spans, 0, 11_000) == pytest.approx(
        (2000 + 1500 + 800) / 3 * 1e-6)
    assert tick_host_ms(spans, 0, 400) is None
    assert tick_host_ms(_program(_without_program(tr).host), *WIN) is None


def test_gap_attribution_names_program_spans(tr):
    """Each idle instant goes to the innermost span, the program's or the
    benchmark's; without the program's spans their share falls to
    ``bench.window`` (3850 ns, of which 2850 move)."""
    d0 = tr.devices["/device:TPU:0"]
    got = dict(trace.attribute_gaps(d0, tr.host, *WIN, top=20))
    want = {"bench.window": 1000, "repro.serve.update": 800,
            "repro.serve.decode": 500, "repro.serve.wait": 500,
            "repro.serve.admit": 470, "bench.decode": 350,
            "repro.serve.readback": 300, "repro.serve.prefill": 250,
            "bench.chunk": 200, "bench.activate_slot": 200,
            "bench.prefill_tick": 50, "repro.serve.tick": 30}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(4650e-9)     # all idle time
    bare = dict(trace.attribute_gaps(d0, _without_program(tr).host, *WIN,
                                     top=20))
    assert bare == pytest.approx({k: v * 1e-9 for k, v in {
        "bench.window": 3850, "bench.decode": 350, "bench.chunk": 200,
        "bench.activate_slot": 200, "bench.prefill_tick": 50}.items()})


def _ctx(cell_name, t, window):
    cell = harness.resolve(cell_name)
    ctx = {"trace": t, "trace_window": window, "devices": sorted(t.devices),
           "config": cell.config, "traffic": cell.traffic,
           "peaks": harness.peaks("TPU v5 lite"), "chips": 1}
    if cell.traffic["kind"] == "serve":
        lo, hi = window
        rows = [(lo + i * (hi - lo) / 8, 3, 300 + 3 * i, i // 3)
                for i in range(-2, 11)]
        ctx.update(kind="serve", ticks=rows, host_window=(lo, hi))
    else:
        ctx.update(kind="train", tokens_per_step=8 * 1024,
                   traced={"steps": 1})
    return ctx


def _with_spans(cell_name, tr):
    """The cell's trace with the hand-made program spans in its window."""
    if cell_name == SERVE:
        return tr, WIN
    chip = trace.load_json(DATA / "chip_train_excerpt.json")
    ops = next(iter(chip.devices.values()))
    lo, hi = min(e.start for e in ops), max(e.end for e in ops)
    moved = [trace.Event(e.name, lo + e.start, e.dur)
             for e in _program(tr.host)]
    host = sorted(chip.host + moved, key=lambda e: e.start)
    return trace.Trace(devices=chip.devices, host=host), (lo, hi)


@pytest.mark.parametrize("metric", sorted(
    m["name"] for m in harness.benchmark()["per_layer"]
    if m["name"] not in NEW))
def test_existing_readers_unmoved_by_program_spans(metric, tr):
    entry = next(m for m in harness.benchmark()["per_layer"]
                 if m["name"] == metric)
    cell_name = entry["workloads"][0]
    t, window = _with_spans(cell_name, tr)
    assert _program(t.host)
    reader = harness.metric_reader(metric)
    with_spans = reader.read(_ctx(cell_name, t, window))
    assert with_spans is not None
    assert with_spans == reader.read(_ctx(cell_name, _without_program(t),
                                          window))


def _profile(tmp_path, program: bool):
    f = jax.jit(lambda x: x @ x)
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        if program:
            with jax.profiler.StepTraceAnnotation("repro.serve.tick",
                                                  step_num=0):
                y = f(jnp.ones((8, 8)))
                with jax.profiler.TraceAnnotation("repro.serve.readback"):
                    y.block_until_ready()
        else:
            f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(tmp_path)
    return {"kind": "serve", "trace": t,
            "trace_window": t.span("bench.window")}


def test_program_spans_of_a_cpu_profile(tmp_path, monkeypatch):
    """Found in the profile the run's trace came from; none in a program
    without them (the reader then gives nothing); a profile of another
    run is refused."""
    ctx = _profile(tmp_path / "a", program=True)
    assert [e.name for e in program_spans.load(ctx, tmp_path / "a")] == [
        "repro.serve.tick", "repro.serve.readback"]
    reader = harness.metric_reader("serve_chat.tick_host_ms")
    monkeypatch.setattr(program_spans, "TRACES", tmp_path / "a")
    assert reader.read(ctx) > 0
    bare = _profile(tmp_path / "b", program=False)
    monkeypatch.setattr(program_spans, "TRACES", tmp_path / "b")
    assert program_spans.load(bare) == [] and reader.read(bare) is None
    with pytest.raises(harness.BenchError, match="not the profile"):
        program_spans.load(ctx)


def test_serve_driver_traced_rehearsal(smoke_cell, tmp_path, monkeypatch):
    """The serve driver traced at smoke size on the CPU: the program's
    spans are in the profile, ticks lie wholly inside the traced part, and
    the tick's host time reads, under the longest tick."""
    cell = smoke_cell(SERVE)
    cell.traffic = {**cell.traffic, "trace_at": 0.2, "trace_s": 0.5}
    out = cell.driver.run(cell, SEED, 0.5, tmp_path, time.perf_counter(),
                          jax.devices()[:1])
    assert out["failed"] == 0
    t = trace.load(tmp_path)
    ctx = {"trace": t, "trace_window": t.span("bench.window"),
           "devices": [], "config": cell.config, "traffic": cell.traffic,
           "chips": 1, **out["layer"]}
    spans = program_spans.load(ctx, tmp_path)
    names = {e.name for e in spans}
    assert {"repro.serve.tick", "repro.serve.admit", "repro.serve.prefill",
            "repro.serve.decode", "repro.serve.readback",
            "repro.serve.update"} <= names
    lo, hi = ctx["trace_window"]
    ticks = [e for e in spans if e.name == "repro.serve.tick"
             and lo <= e.start and e.end <= hi]
    assert ticks
    monkeypatch.setattr(program_spans, "TRACES", tmp_path)
    ms = harness.metric_reader("serve_chat.tick_host_ms").read(ctx)
    assert 0 < ms < max(e.dur for e in ticks) * 1e-6
