"""serve_chat.device_idle: the share of the traced part of the serving
window in which no operation runs on the chip (1 less the union of the
device's operation intervals).  Moves ``serve_tpot_p95_ms``."""
from bench import trace


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    lo, hi = ctx["trace_window"]
    busy = [trace.busy_ns(ctx["trace"].devices[d], lo, hi)
            for d in ctx["devices"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
