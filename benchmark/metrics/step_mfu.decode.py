"""Model FLOPs of every prefill and decode token of the window (2 per
weight multiply-add plus attention over each token's KV depth, from
shapes) over the window times the chip's bf16 peak."""

from benchmark import work


def read(ctx):
    if not ctx.window_s:
        return None
    flops = work.model_flops(ctx.dm, ctx.work["decode_kv"], ctx.work["prefill_lens"])
    return 100.0 * flops / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
