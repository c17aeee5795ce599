"""ring_gather_roofline: the Pallas ``ring_gather`` kernel's share of its
roofline.  Each call moves one whole version of a packed ring group from
HBM to HBM: the row read and written once.  The ring packs every leaf of one
(delay, dtype) into one group, padded to whole (8, 128) tiles; the cells
that list this metric have one delay and float32 weights, so one group of
the padded parameter count.  The least time is those bytes over the HBM
bandwidth (no operations), summed over the calls, over the calls' summed
device time.  Moves ``train_tok_s``."""
import math

from bench import trace, weights

# the custom call is named after the kernel's Python function
PATTERN = r"^ring_gather\b"
TILE = 8 * 128


def call_bytes(config: dict) -> int:
    n = sum(math.prod(shape)
            for shape, _, _ in weights.layout(config).values())
    padded = n + (-n) % TILE
    return 2 * padded * 4


def read(ctx):
    if ctx["kind"] != "train":
        return None
    lo, hi = ctx["trace_window"]
    ns, calls = 0.0, 0
    for d in ctx["devices"]:
        t, n = trace.op_ns(ctx["trace"].devices[d], PATTERN, lo, hi)
        ns, calls = ns + t, calls + n
    if calls == 0:
        return None
    least = calls * call_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ns * 1e-9)
