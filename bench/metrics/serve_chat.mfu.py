"""serve_chat.mfu: the share of the chip's peak that the serving ticks in
the traced part of the window needed, over that part's length.

Each decode step needs the matrix products of the sequences live in it
(2 flops a weight a sequence) and their attention over the positions each
has cached; it reads every weight once at the compute dtype and the keys and
values of those live positions only, so the count is the same whatever
kernels compute it.  Each prefill chunk needs the products of its tokens and
their attention within the chunk (the context it attends to is left out, a
floor) and reads the weights once.  A call's least time is the larger of
operations over the bf16 peak and bytes over the HBM bandwidth.  Live slots
and positions come from the engine's state at each step hook.  Moves
``serve_tpot_p95_ms``."""
from bench import counts


def tick_work(config: dict, live: int, kv_positions: float) -> tuple:
    flops = (2.0 * counts.matmul_params(config) * live
             + counts.attn_flops(config, kv_positions))
    return flops, counts.weight_bytes(config) + counts.kv_bytes(
        config, kv_positions)


def chunk_work(config: dict, chunk: int) -> tuple:
    flops = (2.0 * counts.matmul_params(config) * chunk
             + counts.attn_flops(config, chunk * (chunk + 1) / 2))
    return flops, counts.weight_bytes(config)


def read(ctx):
    if ctx["kind"] != "serve" or not ctx.get("ticks") or not ctx["host_window"]:
        return None
    on, off = ctx["host_window"]
    rows, cfg, peaks = ctx["ticks"], ctx["config"], ctx["peaks"]
    C = ctx["traffic"]["engine"]["prefill_chunk"]
    least = 0.0
    for prev, row in zip(rows, rows[1:]):
        if not on <= row[0] <= off:
            continue
        least += counts.roofline_s(*tick_work(cfg, prev[1], prev[2] + prev[1]),
                                   peaks)
        least += (row[3] - prev[3]) * counts.roofline_s(*chunk_work(cfg, C),
                                                        peaks)
    return 100.0 * least / (off - on)
