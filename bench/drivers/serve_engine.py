"""Driver: open-loop requests through the program's serving engine.

Set-up builds one ``repro.serve.ServeEngine`` over weights made from the
seed and serves a few warm-up requests through it, so every program the
window runs (prefill chunk, decode step, activation, eviction, sampling)
is compiled or loaded before the clock starts.  The window is one call of
``ServeEngine.run`` with the window's requests, due from its start through
``--seconds``; it lasts until the last of them has finished.

Every request is timed from its scheduled arrival, so a stall delays every
request queued behind it.  The traffic file gives the engine's settings, the
rate and the length distributions.
"""
from __future__ import annotations

import gc
import time

import jax

from bench import compare, generator, harness, program, reference, weights

# the engine's own calls wrapped in benchmark spans in a traced run, so the
# device's idle gaps can be put down to what the host was doing
SPANS = ("_decode", "_chunk", "_activate", "_evict", "_sample",
         "_try_admit", "_prefill_tick", "_cow_tick", "_activate_slot",
         "_retire")


def build_engine(cell: harness.Cell, seed: int):
    from repro.serve import ServeConfig, ServeEngine
    cfg = program.model_config(cell.config)
    e = cell.traffic["engine"]
    scfg = ServeConfig(batch_size=e["batch_size"], page_size=e["page_size"],
                       cache_len=e["cache_len"],
                       prefill_chunk=e["prefill_chunk"])
    return ServeEngine(cfg, weights.make(cell.config, seed), scfg)


def _wrap(engine) -> None:
    for name in SPANS:
        fn = getattr(engine, name)

        def spanned(*a, _fn=fn, _label=f"bench.{name.lstrip('_')}", **k):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **k)
        setattr(engine, name, spanned)


class _Ticks:
    """The step hook of a traced run: host time and live work of every
    decode step, and the profiler started and stopped inside the window."""

    def __init__(self, engine, trace_dir, start_s: float, length_s: float):
        self.engine, self.dir = engine, trace_dir
        self.start_s, self.length_s = start_s, length_s
        self.rows: list[tuple] = []     # (t, live, sum of positions, chunks)
        self.t0 = None
        self.window = None              # (t_on, t_off) host clock
        self.span = None

    def __call__(self, i: int) -> None:
        now = time.perf_counter()
        eng = self.engine
        live = [b for b, s in enumerate(eng.slots)
                if s is not None and s.phase != "prefill"]
        self.rows.append((now, len(live), int(eng._pos[live].sum()),
                          eng.prefill_chunks))
        if self.t0 is None:
            self.t0 = now
        if self.span is None and self.window is None \
                and now - self.t0 >= self.start_s:
            jax.profiler.start_trace(str(self.dir))
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.window = (time.perf_counter(), None)
        elif self.span is not None \
                and now - self.window[0] >= self.length_s:
            self.span.__exit__(None, None, None)
            self.span = None
            self.window = (self.window[0], time.perf_counter())
            jax.profiler.stop_trace()

    def close(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            self.window = (self.window[0], time.perf_counter())
            jax.profiler.stop_trace()


def run(cell: harness.Cell, seed: int, seconds: float, trace_dir,
        t_start: float, devices) -> dict:
    t = cell.traffic
    engine = build_engine(cell, seed)
    engine.run(generator.warmup_requests(t, cell.config, seed))
    reqs = generator.serve_requests(t, cell.config, seed, seconds)
    counter = harness.CompileCounter()
    setup_s = time.perf_counter() - t_start

    ticks = None
    if trace_dir is not None:
        _wrap(engine)
        ticks = _Ticks(engine, trace_dir, t["trace_at"] * seconds,
                       t["trace_s"])
    steps0, chunks0 = engine.decode_steps, engine.prefill_chunks
    report = engine.run(reqs, step_hook=ticks)
    if ticks is not None:
        ticks.close()
    compiles_in_window = counter.n
    finished = {f.rid: f for f in engine._finished}

    by_rid = {r.rid: r for r in reqs}
    failed = sum(1 for r in reqs if r.rid not in finished
                 or len(finished[r.rid].tokens) != r.gen_len)
    done = [finished[r.rid] for r in reqs if r.rid in finished]
    ttft = [f.t_first - f.arrival for f in done]
    tpot = [(f.t_done - f.t_first) / (len(f.tokens) - 1)
            for f in done if len(f.tokens) > 1]
    tokens = sum(len(by_rid[f.rid].prompt) + len(f.tokens) for f in done)
    e2e = {"serve_ttft_p95_ms": 1e3 * harness.quantile(ttft, 0.95),
           "serve_tpot_p95_ms": 1e3 * harness.quantile(tpot, 0.95),
           "serve_tok_s": tokens / report.duration}

    peak = harness.memory_peak_bytes(devices)
    sample = compare.sample_requests(
        {f.rid: len(by_rid[f.rid].prompt) + len(f.tokens) for f in done},
        seed, int(t["check_requests"]))
    served = {rid: (by_rid[rid].prompt, finished[rid].tokens)
              for rid in sample}
    del engine, finished, done
    gc.collect()

    w = weights.make(cell.config, seed)
    gaps = [compare.served_gaps(reference.served_logits(
        cell.config, w, p, s), s) for p, s in served.values()]
    del w
    readings = {"logit_gap": float(max(g.max() for g in gaps))}
    return {
        "setup_s": setup_s,
        "e2e": e2e,
        "attempted": len(reqs),
        "failed": failed,
        "checks": compare.checks(readings, cell.limits),
        "memory_peak_bytes": peak,
        "notes": {"requests": len(reqs), "duration_s": report.duration,
                  "decode_steps": report.decode_steps - steps0,
                  "prefill_chunks": report.prefill_chunks - chunks0,
                  "utilization": report.utilization,
                  "compared_tokens": int(sum(len(g) for g in gaps)),
                  "compiles_in_window": compiles_in_window,
                  "ttft_p50_ms": 1e3 * harness.quantile(ttft, 0.5),
                  "last_arrival_s": reqs[-1].arrival if reqs else 0.0},
        "layer": {"kind": "serve",
                  "ticks": None if ticks is None else ticks.rows,
                  "host_window": None if ticks is None else ticks.window},
    }
