"""train.mfu: the share of the chips' bf16 peak that the traced steps'
required operations fill, over the traced window's length.

Operations per trained token: 6x the matrix-product weights, the output head
among them, plus causal attention (each query over half the sequence), and
no recomputation (``bench/counts.py``).  Moves ``train_tok_s``.
"""
from bench import counts


def read(ctx):
    traced = ctx.get("traced")
    if ctx["kind"] != "train" or not traced:
        return None
    lo, hi = ctx["trace_window"]
    flops = (counts.train_flops_per_token(ctx["config"], ctx["traffic"]["seq"])
             * ctx["tokens_per_step"] * traced["steps"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / ((hi - lo) * 1e-9 * peak)
