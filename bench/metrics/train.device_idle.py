"""train.device_idle: the share of the traced window in which no operation
runs on a chip, averaged over the cell's chips (1 less the union of the
device's operation intervals over the window).  Moves ``train_tok_s``."""
from bench import trace


def read(ctx):
    if ctx["kind"] != "train":
        return None
    lo, hi = ctx["trace_window"]
    busy = [trace.busy_ns(ctx["trace"].devices[d], lo, hi)
            for d in ctx["devices"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
