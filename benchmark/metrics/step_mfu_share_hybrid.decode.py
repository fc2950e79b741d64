"""Model FLOPs of this chip's share of a hybrid linear/full-attention model
in the traced seconds of the window, over those seconds times the chip's
bf16 peak, counted as ``step_mfu_share.decode`` counts them but layer kind
by layer kind (``work_hybrid.model_flops``): Gated DeltaNet layers their
projections, conv and ~6 FLOPs per state element a token; gated attention
its doubled ``q_proj`` and attention over its own KV depth; the routed
experts at the share of assignments the held experts take, read from the
traced decode steps' expert counts and applied to prefill tokens too."""

import numpy as np

from benchmark import work_hybrid

DECODE = "engine/decode"


def read(ctx):
    dm = ctx.dm
    n = len(ctx.decode_kv_lens)
    decodes = sorted((s for s in ctx.spans if s["name"] == DECODE), key=lambda s: s["t0_ns"])
    if (not getattr(dm, "lin_v_heads", 0) or not n or not ctx.trace_window_s
            or len(ctx.decode_counts) < n or len(decodes) < n):
        return None
    decode_kv = np.concatenate([np.asarray(k, np.float64) for k in ctx.decode_kv_lens])
    share = np.sum(ctx.decode_counts[:n]) / (len(decode_kv) * dm.top_k * dm.n_moe_layers)
    end = decodes[n - 1]["t0_ns"] + decodes[n - 1]["dur_ns"]
    prefill = [int(s["value"]) for s in ctx.spans
               if s["name"] == "engine/prefill" and s["t0_ns"] < end]
    flops = work_hybrid.model_flops(dm, decode_kv, prefill, float(share))
    return 100.0 * flops / (ctx.trace_window_s * ctx.peak["bf16_flops_per_s"])
