"""Readings of the program's telemetry spans (``engine/*``) in the window,
for the metrics of the decode step's host tail.

A program that splits that tail into spans has an ``engine/decode_launch``
span in every decode step; in a window without one (a program without
this tracing) every reading here is None.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

LAUNCH = "engine/decode_launch"
COMPILE_PREFIX = "engine/compile/"  # one span per program built


def _split(ctx) -> bool:
    return any(s["name"] == LAUNCH for s in ctx.spans)


def mean_ms(ctx, name: str) -> Optional[float]:
    """Mean duration of the named span over the window, in ms: one span
    per decode step for the decode tail's spans."""
    if not _split(ctx):
        return None
    durs = [s["dur_ns"] for s in ctx.spans if s["name"] == name]
    return float(np.mean(durs)) * 1e-6 if durs else None


def compiles(ctx) -> Optional[float]:
    """Programs built in the window (a compile, or a load from the
    persistent compile cache)."""
    if not _split(ctx):
        return None
    return float(sum(s["name"].startswith(COMPILE_PREFIX) for s in ctx.spans))
