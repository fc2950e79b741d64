"""Model FLOPs of this chip's share of the model in the traced seconds of
the window, over those seconds times the chip's bf16 peak: every prefill
and decode token's attention (at its KV depth), dense lead layer, shared
experts, router and LM head (``work.model_flops``), with the routed
experts counted only for the assignments the held experts take.  That
share of a row's routed assignments is read from the traced decode steps
(their expert counts over live rows x top_k x MoE layers; a slot left idle
for a step, rare in a closed loop, is routed too) and applied to prefill
tokens as well.  It reads the traced seconds, where the decode steps' KV
depths are recorded, and not step_mfu.decode's rest of the window, which
counts the profiler's export of the trace."""

import numpy as np

from benchmark import work

DECODE = "engine/decode"


def read(ctx):
    n = len(ctx.decode_kv_lens)
    decodes = sorted((s for s in ctx.spans if s["name"] == DECODE), key=lambda s: s["t0_ns"])
    if not n or not ctx.trace_window_s or len(ctx.decode_counts) < n or len(decodes) < n:
        return None
    dm = ctx.dm
    decode_kv = np.concatenate([np.asarray(k, np.float64) for k in ctx.decode_kv_lens])
    share = np.sum(ctx.decode_counts[:n]) / (len(decode_kv) * dm.top_k * dm.n_moe_layers)
    end = decodes[n - 1]["t0_ns"] + decodes[n - 1]["dur_ns"]
    prefill = [int(s["value"]) for s in ctx.spans
               if s["name"] == "engine/prefill" and s["t0_ns"] < end]
    n_tokens = len(decode_kv) + sum(prefill)
    routed = 2.0 * work.expert_params(dm) * dm.top_k * dm.n_moe_layers * n_tokens
    flops = work.model_flops(dm, decode_kv, prefill) - (1.0 - share) * routed
    return 100.0 * flops / (ctx.trace_window_s * ctx.peak["bf16_flops_per_s"])
