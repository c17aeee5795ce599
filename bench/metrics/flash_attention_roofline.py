"""flash_attention_roofline: the Pallas flash-attention forward kernel's
share of its roofline in the training step.  One call is one layer's causal
attention over the batch; its operations and bytes are the architecture's
(``counts.attn_call``: for a decoder with ``H`` query and ``KV`` key/value
heads of ``hd``, 4 B H hd S^2 / 2 operations, q.k and p.v with each query
over the positions it may see, counted once, and q, k, v and the output
once at the compute dtype).  The least time is the larger of operations
over the bf16 peak and bytes over the HBM bandwidth, summed over the calls,
over their summed device time.  Moves ``train_tok_s``."""
from bench import counts, trace

PATTERN = r"^flash_attention\b"


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
    flops, bytes_ = counts.attn_call(ctx["config"], t["batch"], t["seq"])
    least = calls * counts.roofline_s(flops, bytes_, ctx["peaks"])
    return 100.0 * least / (ns * 1e-9)
