"""serve_chat.tick_ms: the mean host-clock interval between decode steps
while a sequence is live, from the engine's step hook, over the whole
window less its traced part (the profiler slows the host there).  Moves
``serve_tpot_p95_ms``."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx.get("ticks") or not ctx["host_window"]:
        return None
    on, off = ctx["host_window"]
    gaps = [row[0] - prev[0] for prev, row in zip(ctx["ticks"], ctx["ticks"][1:])
            if prev[1] > 0 and (row[0] < on or prev[0] > off)]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
