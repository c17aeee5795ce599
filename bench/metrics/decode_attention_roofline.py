"""decode_attention_roofline: the paged decode attention's share of its
roofline: the keys and values of the live positions (the only bytes the
attention needs) over the HBM bandwidth, over the summed device time of the
decode-attention and page-gather kernels that the decode steps run (the
gather materialises the paged ring the attention then reads).  Prefill
chunks' page gathers share the kernel's name and are in the time, not in
the bytes, so the share is a floor.  Live positions come from the engine's
state at each step hook in the traced part of the window.  Moves
``serve_tpot_p95_ms``."""
from bench import counts, trace

PATTERN = r"^(decode_attention|page_gather)\b"


def read(ctx):
    if ctx["kind"] != "serve" or not ctx.get("ticks") or not ctx["host_window"]:
        return None
    on, off = ctx["host_window"]
    rows = ctx["ticks"]
    kv = sum(prev[2] + prev[1] for prev, row in zip(rows, rows[1:])
             if on <= row[0] <= off)
    lo, hi = ctx["trace_window"]
    ns, calls = 0.0, 0
    for d in ctx["devices"]:
        t, n = trace.op_ns(ctx["trace"].devices[d], PATTERN, lo, hi)
        ns, calls = ns + t, calls + n
    if calls == 0 or kv == 0:
        return None
    least = counts.kv_bytes(ctx["config"], kv) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ns * 1e-9)
