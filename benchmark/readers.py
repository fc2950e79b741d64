"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader gets the run's context and returns a number, or None where it
finds nothing to read (no trace, no such operation in it, no span).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from . import trace as tr

# how the program's programs and kernels are named in the device trace
DECODE_PROGRAM = "decode_step"
PREFILL_PROGRAM = "prefill_chunk"
EXPERT_KERNELS = ("swiglu_gmm_capacity", "swiglu_gemv", "gmm_capacity", "expert_gemv")
ATTENTION_KERNELS = ("decode_attention", "decode_attention_paged")


def device_events(ctx):
    if ctx.trace is None or ctx.dev not in ctx.trace.ops:
        return None, None
    return ctx.trace.ops[ctx.dev], ctx.trace.modules.get(ctx.dev)


def idle_share(ctx) -> Optional[float]:
    ops, _ = device_events(ctx)
    if ops is None or not len(ops) or not ctx.trace_window_s:
        return None
    return 100.0 * (1.0 - tr.busy_ns(ops) * 1e-9 / ctx.trace_window_s)


def programs(ctx, name: str):
    _, mods = device_events(ctx)
    if mods is None:
        return None
    sel = mods.matching([name])
    return sel if len(sel) else None


def kernel_time_in(ctx, program: str, kernels) -> Optional[float]:
    """Seconds of device time of the named kernels inside runs of the
    named program."""
    ops, _ = device_events(ctx)
    progs = programs(ctx, program)
    if ops is None or progs is None:
        return None
    k = tr.inside(ops.of_kind(kernels), progs)
    return k.total() * 1e-9 if len(k) else None


def expert_stack_shapes(dm):
    """Result shapes of an operation that moves one layer's whole routed
    expert stack (``w_gate``/``w_up`` and ``w_down``)."""
    E, d, f = dm.n_experts, dm.d, dm.d_expert
    return [(E, d, f), (E, f, d)]


def expert_path_time(ctx) -> Optional[float]:
    """Seconds of device time of the expert path inside runs of the decode
    program: the expert kernels, and the operations outside them that move
    a layer's whole expert stack (the per-layer slice of the stacked
    weights that the layer loop makes).  None without the kernels."""
    ops, _ = device_events(ctx)
    progs = programs(ctx, DECODE_PROGRAM)
    if ops is None or progs is None:
        return None
    kernels = tr.inside(ops.of_kind(EXPERT_KERNELS), progs)
    if not len(kernels):
        return None
    leaves = ops.leaves()
    others = leaves.select([tr.op_kind(n) not in EXPERT_KERNELS for n in leaves.names])
    stacks = tr.inside(others.of_shape(expert_stack_shapes(ctx.dm)), progs)
    return (kernels.total() + stacks.total()) * 1e-9


def host_ms_per_step(ctx) -> Optional[float]:
    """Mean of engine/step minus its engine/prefill and engine/decode
    children, over the window's steps."""
    steps = [s for s in ctx.spans if s["name"] == "engine/step"]
    if not steps:
        return None
    kids = [s for s in ctx.spans if s["name"] in ("engine/prefill", "engine/decode")]
    starts = np.array([s["t0_ns"] for s in steps], np.int64)
    own = np.array([s["dur_ns"] for s in steps], np.float64)
    for k in kids:
        i = int(np.searchsorted(starts, k["t0_ns"], side="right")) - 1
        if i >= 0:
            own[i] -= k["dur_ns"]
    return float(own.mean()) * 1e-6
