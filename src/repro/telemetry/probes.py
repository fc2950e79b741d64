"""Measured stage-timing probes for the decode hot path.

The compiled decode step fuses dispatch, head-path grouped SwiGLU,
tail-path streaming GEMV, and attention into one jit function — per-stage
wall times cannot be read off the hot path without breaking the fusion
that PR 5 built.  Instead, :class:`StageProbes` runs each stage
*standalone* ("timed decode-step cells", ROADMAP open item 1) with
representative shapes through the exact stage code the step executes
(:func:`repro.models.moe.tail_stage` / :func:`head_stage` /
:func:`dispatch`, :func:`repro.kernels.ref.decode_attention_ref`), off
the critical path on the serving engine's EMA refresh cadence.

Each probe is wrapped in a telemetry span whose ``value`` carries the
probed token count, so:

* the trace timeline shows measured ``stage/*`` cells next to the
  ``engine/step`` spans they decompose;
* :class:`repro.telemetry.TimingFeed` can aggregate the tail-stage spans
  into ``CostTable.update_batch`` — the measured replacement for the
  DRAM-model proxy (``cost_source="measured"``).

Weights/activations are synthetic (stage timings depend on shapes and
kernels, not values); jitted probes are memoized per shape and shapes are
bucketed (powers of two) so compile churn is bounded.  The first call at
a new shape compiles + warms up untimed — spans only ever measure
execution.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from .core import Telemetry

DISPATCH_SPAN = "stage/dispatch"
HEAD_SPAN = "stage/head_gmm"
TAIL_SPAN = "stage/tail_gemv"
ATTN_SPAN = "stage/attention"

_HEAD_GROUPS = 8  # fixed probe group count (counts pad/clip to this)


def _pow2_bucket(n: int, lo: int = 8, hi: int = 4096) -> int:
    b = lo
    while b < min(n, hi):
        b *= 2
    return b


class StageProbes:
    """Executes one decode stage standalone under jit and records the
    measured duration as a telemetry span.

    Parameters mirror one MoE layer's dims (``d_model``/``d_expert``) plus
    optional attention dims ``(n_heads, n_kv_heads, d_head)`` for the
    attention probe.  Requires an *enabled* :class:`Telemetry` — the spans
    are the measurement record.
    """

    def __init__(
        self,
        d_model: int,
        d_expert: int,
        telemetry: Telemetry,
        attn_dims: Optional[Tuple[int, int, int]] = None,
        seed: int = 0,
    ):
        import jax.numpy as jnp

        self.tel = telemetry
        self.d_model = int(d_model)
        self.d_expert = int(d_expert)
        self.attn_dims = attn_dims
        rng = np.random.default_rng(seed)
        f32 = jnp.float32
        # single-expert weights for the tail probe; _HEAD_GROUPS experts
        # for the head probe (gathered layouts, exactly what the stages eat)
        self._wg1 = jnp.asarray(
            rng.standard_normal((1, d_model, d_expert)) * 0.05, f32
        )
        self._wu1 = jnp.asarray(
            rng.standard_normal((1, d_model, d_expert)) * 0.05, f32
        )
        self._wd1 = jnp.asarray(
            rng.standard_normal((1, d_expert, d_model)) * 0.05, f32
        )
        self._wgh = jnp.asarray(
            rng.standard_normal((_HEAD_GROUPS, d_model, d_expert)) * 0.05, f32
        )
        self._wuh = jnp.asarray(
            rng.standard_normal((_HEAD_GROUPS, d_model, d_expert)) * 0.05, f32
        )
        self._wdh = jnp.asarray(
            rng.standard_normal((_HEAD_GROUPS, d_expert, d_model)) * 0.05, f32
        )
        self._rng = rng
        self._jits: Dict[tuple, tuple] = {}  # key -> (fn, args)
        self.n_probes = 0
        # Fault-injection hook: ``corrupt(span_name, value, dt) -> dt'``
        # rewrites a measured duration before it is recorded — the
        # probe-poison chaos scenario plugs in here, so the *measurement
        # channel* (not the stage code) is what gets attacked and the
        # TimingFeed/health defenses downstream are what's under test.
        self.corrupt: Optional[Callable[[str, float, float], float]] = None

    # ------------------------------------------------------------------
    def _timed(self, span_name: str, value: float, fn, args) -> float:
        """Run ``fn(*args)`` to completion; records the measured duration
        as a span (via the optional :attr:`corrupt` hook) and returns it."""
        import jax

        t0_ns = self.tel._clock() if self.tel.enabled else 0
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        if self.corrupt is not None:
            dt = float(self.corrupt(span_name, value, dt))
        if self.tel.enabled:
            # non-finite corruption cannot be represented in the int64
            # ring; record a zero-duration span (rejected downstream)
            dur = dt if math.isfinite(dt) else 0.0
            self.tel.span_at(span_name, t0_ns * 1e-9, dur, value=value)
        self.n_probes += 1
        return dt

    def _get(self, key, build):
        """Memoized (jitted fn, fixed args); first build warms up untimed."""
        import jax

        hit = self._jits.get(key)
        if hit is None:
            fn, args = build()
            fn = jax.jit(fn)
            jax.block_until_ready(fn(*args))  # compile + warm, untimed
            hit = self._jits[key] = (fn, args)
        return hit

    # ------------------------------------------------------------------
    def tail(self, n_tokens: int) -> float:
        """Measure the tail stage for one expert with ``n_tokens`` rows.

        This is the per-expert "PIM GEMV" cell the cost table is keyed on:
        the span value is ``n_tokens``, so :class:`TimingFeed` feeds the
        measurement straight into ``CostTable.update_batch``.
        """
        from repro.models.moe import tail_stage

        import jax.numpy as jnp

        n = max(int(n_tokens), 1)

        def build():
            toks = jnp.asarray(
                self._rng.standard_normal((n, self.d_model)), jnp.float32
            )
            eids = jnp.zeros((n,), jnp.int32)
            valid = jnp.ones((n,), jnp.int32)
            fn = lambda t, e, v: tail_stage(
                t, self._wg1, self._wu1, self._wd1, e, v
            )
            return fn, (toks, eids, valid)

        fn, args = self._get(("tail", n), build)
        return self._timed(TAIL_SPAN, float(n), fn, args)

    def head(self, counts: Iterable[int]) -> float:
        """Measure the grouped head stage over a compacted hot-expert slab
        shaped like ``counts`` (pad/clip to the fixed probe group count;
        capacity buckets to a power of two).  Span value = total rows."""
        import jax.numpy as jnp

        from repro.models.moe import head_stage

        cs = sorted((int(c) for c in counts if c > 0), reverse=True)
        cs = (cs + [0] * _HEAD_GROUPS)[:_HEAD_GROUPS]
        cap = _pow2_bucket(max(cs) if cs else 1)
        cs = [min(c, cap) for c in cs]

        def build():
            slab = jnp.asarray(
                self._rng.standard_normal((_HEAD_GROUPS, cap, self.d_model)),
                jnp.float32,
            )
            fn = lambda s, sz: head_stage(
                s, self._wgh, self._wuh, self._wdh, sz
            )
            return fn, (slab, jnp.zeros((_HEAD_GROUPS,), jnp.int32))

        fn, (slab, _) = self._get(("head", cap), build)
        sizes = jnp.asarray(cs, jnp.int32)
        return self._timed(HEAD_SPAN, float(sum(cs)), fn, (slab, sizes))

    def dispatch(self, n_tokens: int, n_experts: int, top_k: int) -> float:
        """Measure the routing-dispatch stage at the decode batch shape."""
        import jax.numpy as jnp

        from repro.models.moe import RouterOut, dispatch

        T = max(int(n_tokens), 1)
        cap = _pow2_bucket(max(T * top_k // max(n_experts, 1), 1))

        def build():
            x = jnp.asarray(
                self._rng.standard_normal((T, self.d_model)), jnp.float32
            )
            eidx = jnp.asarray(
                self._rng.integers(0, n_experts, size=(T, top_k)), jnp.int32
            )
            w = jnp.full((T, top_k), 1.0 / top_k, jnp.float32)

            def fn(x, eidx, w):
                counts = (
                    jnp.zeros((n_experts,), jnp.int32)
                    .at[eidx.reshape(-1)]
                    .add(1)
                )
                r = RouterOut(eidx, w, jnp.zeros((), jnp.float32), counts)
                return dispatch(x, r, n_experts, cap).buf

            return fn, (x, eidx, w)

        fn, args = self._get(("dispatch", T, n_experts, top_k, cap), build)
        return self._timed(DISPATCH_SPAN, float(T * top_k), fn, args)

    def attention(self, batch: int, seq: int) -> float:
        """Measure decode attention at (batch, bucketed KV depth)."""
        if self.attn_dims is None:
            return 0.0
        import jax.numpy as jnp

        from repro.kernels import ref

        n_heads, n_kv, d_head = self.attn_dims
        B = max(int(batch), 1)
        S = _pow2_bucket(max(int(seq), 1))

        def build():
            r = self._rng
            q = jnp.asarray(
                r.standard_normal((B, n_heads, d_head)), jnp.float32
            )
            ck = jnp.asarray(
                r.standard_normal((B, n_kv, S, d_head)), jnp.float32
            )
            cv = jnp.asarray(
                r.standard_normal((B, n_kv, S, d_head)), jnp.float32
            )
            lens = jnp.full((B,), S, jnp.int32)
            return ref.decode_attention_ref, (q, ck, cv, lens)

        fn, args = self._get(("attn", B, S), build)
        return self._timed(ATTN_SPAN, float(B * S), fn, args)
