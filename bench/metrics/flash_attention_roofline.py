"""flash_attention_roofline: the Pallas flash-attention forward kernel's
share of its roofline in the training step.  One call is one layer's causal
attention over the batch: operations 4 B H hd S^2 / 2 (q.k and p.v, each
query over the positions it may see, counted once), bytes q, k, v and the
output once at the compute dtype (k and v at their KV heads).  The least
time is the larger of operations over the bf16 peak and bytes over the HBM
bandwidth, summed over the calls, over their summed device time.  Moves
``train_tok_s``."""
from bench import counts, trace, weights

PATTERN = r"^flash_attention\b"


def call_flops_bytes(config: dict, batch: int, seq: int) -> tuple:
    m = weights.dims(config)
    flops = 4.0 * batch * m["H"] * m["hd"] * seq * seq / 2
    elems = batch * seq * m["hd"] * (2 * m["H"] + 2 * m["KV"])
    return flops, elems * counts.BYTES[config["compute_dtype"]]


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
    t = ctx["traffic"]
    flops, bytes_ = call_flops_bytes(ctx["config"], t["batch"], t["seq"])
    least = calls * counts.roofline_s(flops, bytes_, ctx["peaks"])
    return 100.0 * least / (ns * 1e-9)
