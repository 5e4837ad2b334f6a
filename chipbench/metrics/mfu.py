"""mfu: model FLOP/s of the traced window over the chips' bf16 peak, in %.

Model FLOPs per token (chipbench/counts.py) times the tokens the window
trained, over the window's length on the trace's clock, over chips times
peak.  The same whether LSH is on or off, whatever implements a kernel.
"""


def read(ctx):
    rate = ctx.tokens / ctx.trace.window_s
    return 100.0 * ctx.flops_per_token * rate \
        / (ctx.chips * ctx.peaks.bf16_flops)
