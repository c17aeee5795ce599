"""serve_chat.tick_host_ms: the host's own work in a serving tick, on the
device trace's clock.  The mean, over the engine's ``repro.serve.tick`` spans
wholly inside the traced part of the window, of the span's length less the
time its ``repro.serve.readback`` covers (the host waiting for the decode
step's tokens).  What is left is admission, chunk planning and dispatch, the
decode step's inputs and dispatch, activation and the per-slot token loop:
time in which the chip waits unless work is queued.  A program without these
spans gives nothing.  Moves ``serve_tpot_p95_ms``."""
from bench import program_spans, trace

TICK, READBACK = "repro.serve.tick", "repro.serve.readback"


def tick_host_ms(spans, lo: float, hi: float) -> float | None:
    ticks = [e for e in spans
             if e.name == TICK and lo <= e.start and e.end <= hi]
    if not ticks:
        return None
    reads = [e for e in spans if e.name == READBACK]
    host = sum(t.dur - trace.busy_ns(reads, t.start, t.end) for t in ticks)
    return 1e-6 * host / len(ticks)


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return tick_host_ms(program_spans.load(ctx), *ctx["trace_window"])
