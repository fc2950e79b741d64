"""Sieve serving engine: continuous batching + runtime scheduler loop.

This is the runtime-framework half of the paper (§6) in executable form:
per engine step it

  1. admits requests into KV slots and runs (chunked) prefill;
  2. runs one batched decode step — the compiled step returns per-layer
     expert token counts (the routing map ③ of Fig 8);
  3. feeds observed counts into the EMA cost table and runs the Sieve
     scheduler per MoE layer, recording the GPU/PIM partitions and their
     estimated times (on TPU these partitions select grouped-GEMM vs
     streaming-GEMV kernels; the decision trail is exported for analysis);
  4. under ``MoEConfig.expert_exec="dual_path_cost"``, exports the cost
     table + cost model into a device-resident ``SieveState`` on the EMA
     refresh cadence (``sieve_refresh_every`` steps, skipped when the
     table version is unchanged) — the compiled prefill/decode steps read
     it as a fixed-shape array input, so the in-graph split follows the
     learned costs without ever recompiling.

The engine is hardware-agnostic: on this CPU container it serves reduced
models end-to-end (examples/serve_moe.py); on a TPU pod the same engine
drives the jit'd steps built by launch/serve.py.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.cost_model import CostModel, MoELayerSpec, SystemSpec, b200_pim_system
from repro.core.cost_table import CostTable
from repro.core.scheduler import schedule
from repro.core.scheduler_jax import SieveState, make_sieve_state
from repro.faults.health import HealthMonitor
from repro.models.model import LM
from repro.models.sharding import cache_pspecs, to_shardings
from repro.sim.dram import PimGemvModel
from repro.telemetry import StageProbes, Telemetry, TimingFeed
from repro.telemetry import default as default_telemetry
from .batching import BatchingConfig, PagedKVCache, SlotScheduler
from .request import Request

# cost-table feeding modes: "model" synthesizes PIM observations from the
# DRAM-timing proxy (PimGemvModel); "measured" drives the table from
# span-measured tail-stage probe durations (TimingFeed) on the refresh
# cadence — no DRAM-proxy lookups anywhere on the refresh path.
COST_SOURCES = ("model", "measured")

# cap on stage probes per refresh boundary (distinct tail counts measured);
# keeps the off-critical-path probe cost bounded per cadence
_MAX_TAIL_PROBES = 8

# fixed sentinel tail cell probed at every refresh boundary: its measured
# time vs the roofline model proxy is the PIM-health drift signal (a
# stationary ratio — the EMA baseline absorbs the hardware/model scale),
# and it keeps the feed's progress heartbeat alive on idle boundaries
_SENTINEL_TAIL = 1
_SENTINEL_PROBES = 3  # repeats per boundary; the mean damps OS jitter

# "PIM time" exported while the stack is flagged unhealthy: huge but
# finite float32 seconds, so the in-graph argmin picks the minimal
# feasible tail (GPU-only split) without any shape or dtype change — the
# compiled decode step never retraces on a health transition
_PIM_BLOCKED_TIME = 1e9


def greedy_ids(logits: jax.Array, vocab_size: int) -> jax.Array:
    """Each row's greedy token from a step's logits ``(B, S, V_padded)`` at
    the last position: ``(B,)`` int32, the first index on ties, as numpy's
    argmax takes it."""
    return jnp.argmax(logits[:, -1, :vocab_size], axis=-1).astype(jnp.int32)


def decode_program(lm: LM):
    """The function the engine compiles for a decode step: ``lm.decode_step``
    and each row's greedy pick of its logits, as ``((ids, logits), cache,
    aux)``.  A greedy engine copies only the ``(B,)`` ids to the host; one
    that samples on the host copies the logits.  The inner function's name
    keeps the compiled module ``jit_decode_step``, the name a device trace
    finds the decode program by."""

    def decode_step(params, batch, cache):
        logits, cache, aux = lm.decode_step(params, batch, cache)
        return (greedy_ids(logits, lm.arch.vocab_size), logits), cache, aux

    return decode_step


def _placed_cache(lm: LM, make, per_slot: bool = True) -> Any:
    """The cache ``make`` builds, created already sharded by
    ``cache_pspecs`` when the model runs on a mesh (never gathered onto
    one device first).  A paged pool (``per_slot=False``) is shared by
    every slot, so its block dim stays whole on every data shard, as the
    paged kernel's ``shard_map`` expects; only kv heads split."""
    mi = lm.mi
    if mi.mesh is None:
        return make()
    specs = cache_pspecs(
        jax.eval_shape(make), lm.arch,
        data_axes=mi.data_axes if per_slot else (),
        model_axis=mi.model_axis, model_size=mi.ep_size,
    )
    return jax.jit(make, out_shardings=to_shardings(mi.mesh, specs))()


@dataclass
class EngineStats:
    steps: int = 0
    decode_tokens: int = 0
    prefill_tokens: int = 0
    wall_time: float = 0.0
    # capacity/dual-path overflow drops measured in-graph
    # (MoEOut.n_dropped summed over layers), next to the routed totals so
    # drop *rate* can sit beside TTFT/TPOT in reports
    dropped_tokens: int = 0
    routed_tokens: int = 0
    # requests force-finished at the KV capacity (max_seq) — the loud
    # alternative to the old silent clamp-and-overwrite of the last entry
    truncated_requests: int = 0
    # admission-control terminal outcomes: deadline passed while queued /
    # batch request refused at submit under brownout stage 3
    expired_requests: int = 0
    shed_requests: int = 0
    partitions: List[Dict] = field(default_factory=list)

    @property
    def throughput(self) -> float:
        return self.decode_tokens / self.wall_time if self.wall_time else 0.0

    @property
    def drop_rate(self) -> float:
        # defined as 0.0 before any token has been routed — an engine that
        # never generated a token must not divide by zero
        if self.routed_tokens <= 0:
            return 0.0
        return self.dropped_tokens / self.routed_tokens


class ServingEngine:
    def __init__(
        self,
        lm: LM,
        params: Any,
        batching: BatchingConfig,
        policy: str = "sieve",
        system: Optional[SystemSpec] = None,
        greedy: bool = True,
        seed: int = 0,
        sieve_refresh_every: int = 16,
        telemetry: Optional[Telemetry] = None,
        cost_source: str = "model",
        health: Optional[HealthMonitor] = None,
        brownout_batch_max_new: int = 8,
    ):
        if cost_source not in COST_SOURCES:
            raise ValueError(
                f"cost_source must be one of {COST_SOURCES}, got {cost_source!r}"
            )
        self.lm = lm
        self.params = params
        self.cfg = batching
        self.policy = policy
        self.sched = SlotScheduler(batching)
        self.greedy = greedy
        self.rng = np.random.default_rng(seed)
        self.stats = EngineStats()
        self.cost_source = cost_source
        # telemetry: explicit instance wins; otherwise the process default
        # (enabled iff REPRO_TELEMETRY is set — a shared no-op otherwise)
        self.tel = telemetry if telemetry is not None else default_telemetry()

        # paged KV: slots index a shared device block pool through a
        # host-side block table (allocated on admit/decode, freed on
        # retire); dense mode keeps the per-slot (max_seq, ...) buffers
        self.paged: Optional[PagedKVCache] = None
        if batching.paged:
            self.paged = PagedKVCache(batching)
            self.cache = _placed_cache(
                lm, lambda: lm.init_paged_cache(self.paged.n_pool, self.paged.page),
                per_slot=False,
            )
        else:
            self.cache = _placed_cache(
                lm, lambda: lm.init_cache(batching.n_slots, batching.max_seq)
            )
        # The KV cache is donated on both compiled steps (argnum 2): the
        # engine rebinds ``self.cache`` to the returned cache every call,
        # so the stale buffers would otherwise survive as full-cache
        # copies — at decode that is a whole-cache memcpy per step.  With
        # donation XLA aliases cache-in to cache-out and the update is
        # in-place (pinned by tests/test_serving.py::TestBufferDonation).
        self._decode = jax.jit(decode_program(lm), donate_argnums=(2,))
        # the slot is a traced int32, so prefill compiles once per prompt
        # length, not once per (slot, prompt length) pair
        self._prefill_chunk = jax.jit(
            self._prefill_chunk_impl, donate_argnums=(2,)
        )

        # ---- Sieve runtime state (MoE archs only) ----
        arch = lm.arch
        self.is_moe = arch.moe is not None
        # cost-driven in-graph split: the compiled step consumes a
        # device-resident SieveState refreshed on the EMA update cadence
        self.uses_cost_split = (
            self.is_moe and arch.moe.expert_exec == "dual_path_cost"
        )
        self.sieve_refresh_every = max(int(sieve_refresh_every), 1)
        self.sieve_refreshes: List[int] = []  # step indices of re-exports
        self._sieve_state: Optional[SieveState] = None
        self._sieve_version = -1
        self._sieve_gpu_only = False
        # PIM health gate: flipped by _update_pim_health at refresh
        # boundaries; while False the sieve export clamps to GPU-only and
        # the measured feed is quarantined (model-proxy fallback)
        self.pim_healthy = True
        self.health = health
        # cluster-driven brownout stage (0 = healthy .. 3 = shed): stage 1+
        # clamps batch-tier max_new_tokens at submit, stage 2+ forces the
        # GPU-only sieve export, stage 3 refuses new batch requests
        self.brownout_stage = 0
        self.brownout_batch_max_new = max(int(brownout_batch_max_new), 1)
        if cost_source == "measured" and not self.is_moe:
            raise ValueError(
                "cost_source='measured' feeds the MoE cost table; "
                f"arch {arch.name!r} has no MoE layers"
            )
        # measured cost loop (built in the MoE branch below)
        self._probes: Optional[StageProbes] = None
        self._timing_feed: Optional[TimingFeed] = None
        self._pending_tail_counts: set = set()
        self._last_head_counts: List[int] = []
        self._last_decode_batch = 0
        self._last_kv_depth = 1
        # per-layer metric names, built once (f-strings per step add up on
        # a ~5ms decode step)
        self._layer_metric_names: List[tuple] = []
        if self.is_moe:
            self.system = system or b200_pim_system()
            self.layer_spec = MoELayerSpec(
                d_model=arch.d_model,
                d_ff=arch.moe.d_expert,
                n_experts=arch.moe.n_held,
                top_k=arch.moe.top_k,
                n_shared=arch.moe.n_shared,
            )
            self.cost_model = CostModel(system=self.system, layer=self.layer_spec)
            self._pim = (
                PimGemvModel(self.system.pim) if self.system.pim is not None else None
            )
            fallback = (
                self.cost_model.t_pim_gemv_roofline
                if self._pim is None
                else None
            )
            self.cost_table = CostTable(
                fallback=fallback or self.cost_model.t_pim_gemv_roofline
            )
            if cost_source == "measured":
                # the span buffer is the measurement record: if the caller
                # left telemetry disabled, the measured loop still needs a
                # live instance of its own (private — nothing else reads it)
                if not self.tel.enabled:
                    self.tel = Telemetry(enabled=True)
                attn = arch.attn
                attn_dims = (
                    (attn.n_heads, attn.n_kv_heads, attn.d_head)
                    if attn.kind == "gqa"
                    else None
                )
                self._probes = StageProbes(
                    arch.d_model,
                    arch.moe.d_expert,
                    self.tel,
                    attn_dims=attn_dims,
                    seed=seed,
                )
                self._timing_feed = TimingFeed(self.cost_table, self.tel)
                # health detection on the measured loop (the only cost
                # source that can silently break): sentinel drift vs the
                # roofline proxy + a feed-progress staleness watchdog.
                # PimGemvModel is never consulted — the measured path
                # stays DRAM-proxy-free even for its health reference.
                if self.health is None:
                    self.health = HealthMonitor(
                        threshold=4.0,
                        alpha=0.2,
                        warmup=1,
                        confirm=1,
                        recover=2,
                        stale_after=2,
                        telemetry=self.tel,
                    )
                self._roofline_t1 = self.cost_model.t_pim_gemv_roofline(
                    _SENTINEL_TAIL
                )
            if self.uses_cost_split:
                # per-expert counts are bounded by the step's token count
                # (n_slots decode tokens / max_seq prefill tokens); the jit
                # split clamps larger indices to the last table entry
                self._sieve_max_count = min(
                    4096, max(batching.n_slots, batching.max_seq, 64)
                )
                self._refresh_sieve_state(step=0)
        # build-time record: MoE layers whose expert kernels read the
        # layer-stacked weights in place (0 where each layer is sliced)
        self.tel.gauge(
            "engine/moe_layers_in_place", float(lm.moe_layers_in_place())
        )
        if self.is_moe:
            # the experts each MoE layer holds (all of the router's, or
            # one chip's share of an expert-parallel group)
            self.tel.gauge("engine/moe_experts_held", float(arch.moe.n_held))
        if arch.family == "linear_hybrid":
            # fixed-size per-slot state beside the KV: the linear layers'
            # recurrent state and conv tails, for every slot
            self.tel.gauge(
                "engine/recurrent_state_bytes",
                float(self.cache["recurrent"].nbytes + self.cache["conv"].nbytes),
            )

    # ------------------------------------------------------------------
    def _refresh_sieve_state(self, step: int, gpu_only: bool = False) -> None:
        """Re-export (CostTable, CostModel) into the device-resident state.

        Fixed shapes (table depth and packed-params length never change),
        so the compiled prefill/decode steps see the same signature and a
        refresh can never trigger a retrace — the split simply reads new
        numbers.  Skipped when the table has not changed since the last
        export.

        The packed ``t_comm`` is evaluated at the decode-step nominal
        (``n_slots * top_k`` routed tokens); on this single-device engine
        (``ep_degree=1``) it is exactly 0 either way.  A multi-device
        engine feeding long prefills should export a per-phase state
        (ROADMAP open item) so the prefill split's comm floor is not
        understated.

        ``gpu_only=True`` (PIM flagged unhealthy) exports huge-but-finite
        PIM times instead of the table, so the in-graph argmin clamps to
        the minimal feasible tail — same shapes, same compiled step, zero
        jit-cache misses on a health transition.
        """
        if (
            self._sieve_state is not None
            and self.cost_table.version == self._sieve_version
            and gpu_only == self._sieve_gpu_only
        ):
            return
        stale = self._sieve_state
        state = make_sieve_state(
            self.cost_table,
            self.cost_model,
            self._sieve_max_count,
            total_routed_tokens=self.cfg.n_slots
            * self.lm.arch.moe.top_k,
        )
        if gpu_only:
            blocked = np.full(
                state.pim_time_by_count.shape, _PIM_BLOCKED_TIME, np.float32
            )
            blocked[0] = 0.0  # a 0-token expert still costs nothing
            state = state._replace(pim_time_by_count=blocked)
        self._sieve_state = jax.device_put(state)
        self._sieve_version = self.cost_table.version
        self._sieve_gpu_only = gpu_only
        self.sieve_refreshes.append(step)
        # donate the stale state: its device buffers can never be read
        # again (the engine always passes the current state), so free
        # them eagerly instead of waiting for GC — long-lived engines
        # otherwise hold two table exports alive per refresh.
        if stale is not None:
            for leaf in jax.tree.leaves(stale):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()

    # ------------------------------------------------------------------
    def _prefill_chunk_impl(self, params, batch, cache, slot: jax.Array):
        """Prefill one whole prompt into slot ``slot`` (B=1 path; ``slot``
        is a traced int32 scalar).  Returns ``((ids, logits), cache, aux)``
        as the decode program does: the ``(1,)`` greedy id of the prompt's
        next token beside its logits."""
        block_ids = batch.pop("block_ids", None)  # paged: slot's block-table row
        logits, req_cache, aux = self.lm.prefill(params, batch)

        if block_ids is None:

            def insert(slot_leaf, req_leaf):
                # slot_leaf: (L, B_slots, ...); req_leaf: (L, 1, ...) with
                # the prompt's P rows where the slot holds max_seq
                start = (0, slot) + (0,) * (slot_leaf.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    slot_leaf, req_leaf.astype(slot_leaf.dtype), start
                )

        else:
            page = self.paged.page

            def insert(pool_leaf, req_leaf):
                # pool_leaf: (L, n_pool, Kv, page, dh); req_leaf:
                # (L, 1, Kv, P, dh).  Pad the prompt's KV rows to whole
                # pages and scatter them over the slot's allocated blocks
                # (nbp is trace-static: the prompt length is a jit key)
                L, _, Kv, P, dh = req_leaf.shape
                nbp = -(-P // page)
                rows = jnp.pad(
                    req_leaf[:, 0], ((0, 0), (0, 0), (0, nbp * page - P), (0, 0))
                )
                pages = rows.reshape(L, Kv, nbp, page, dh).transpose(0, 2, 1, 3, 4)
                return pool_leaf.at[:, block_ids[:nbp]].set(
                    pages.astype(pool_leaf.dtype)
                )

        new_cache = jax.tree.map(insert, cache, req_cache)
        ids = greedy_ids(logits, self.lm.arch.vocab_size)
        return (ids, logits), new_cache, aux

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Enqueue ``req``; returns False when admission refused it
        (brownout stage 3 sheds the batch tier at the door)."""
        if len(req.prompt) > self.cfg.max_seq:
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds the KV capacity "
                f"max_seq={self.cfg.max_seq}; raise BatchingConfig.max_seq "
                "or truncate the prompt"
            )
        if req.priority == "batch":
            if self.brownout_stage >= 3:
                self.stats.shed_requests += 1
                if self.tel.enabled:
                    self.tel.counter("engine/shed_requests")
                return False
            if self.brownout_stage >= 1:
                # degrade, don't refuse: the batch tier keeps flowing but
                # each request's decode budget is clamped
                req.max_new_tokens = min(
                    req.max_new_tokens, self.brownout_batch_max_new
                )
        self.sched.submit(req)
        return True

    def set_brownout_stage(self, stage: int) -> None:
        """Adopt a cluster-level brownout stage (idempotent).

        Stage 2+ immediately re-exports the sieve state GPU-only through
        the fixed-shape refresh path — same compiled step, zero jit-cache
        misses — shifting expert work off the PIM stack while the cluster
        is saturated; dropping back below 2 restores the table-driven
        split at the same cost.
        """
        stage = max(int(stage), 0)
        if stage == self.brownout_stage:
            return
        self.brownout_stage = stage
        if self.uses_cost_split:
            self._refresh_sieve_state(
                step=self.stats.steps,
                gpu_only=(stage >= 2) or not self.pim_healthy,
            )
        if self.tel.enabled:
            self.tel.gauge("engine/brownout_stage", float(stage))

    def _run_sieve(self, counts_per_layer: np.ndarray) -> List[List[int]]:
        """Host-side scheduler pass over this step's per-layer counts;
        returns each layer's head (grouped-GEMM) expert set."""
        kw = {}
        if self.policy in ("dual_threshold", "dual_cost"):
            # the host decision trail must evaluate the same feasibility
            # window as the compiled step's in-graph split
            moe = self.lm.arch.moe
            kw = {
                "tail_tokens": moe.dual_tail_tokens,
                "max_head": moe.dual_max_head,
            }
        measured = self.cost_source == "measured"
        quarantined = (
            measured
            and self._timing_feed is not None
            and self._timing_feed.quarantined
        )
        heads = []
        for li, counts in enumerate(counts_per_layer):
            part = schedule(
                self.policy, counts, self.cost_model, self.cost_table, **kw
            )
            if measured:
                # queue the tail set's token counts for the refresh-cadence
                # probe pass — the DRAM proxy is never consulted here.
                # Probing continues even under quarantine: the raw
                # measurements are what the health monitor needs to see
                # the fault clear.
                for e in part.pim_experts:
                    n = int(counts[e])
                    if n > 0:
                        self._pending_tail_counts.add(n)
                self._last_head_counts = [
                    int(counts[e]) for e in part.gpu_experts if counts[e] > 0
                ]
                if quarantined:
                    # graceful degradation: the measured feed is untrusted,
                    # so the table falls back to the roofline model proxy
                    # (its own fallback estimator) until clearance re-warms
                    # the measured path
                    for e in part.pim_experts:
                        n = int(counts[e])
                        if n > 0:
                            self.cost_table.update(
                                n, self.cost_model.t_pim_gemv_roofline(n)
                            )
            elif self._pim is not None:
                # observe "PIM" execution times for the chosen set (from
                # the DRAM-timing model — the synthetic-oracle fallback)
                for e in part.pim_experts:
                    n = int(counts[e])
                    if n > 0:
                        self.cost_table.update(
                            n, self._pim.expert_time(self.layer_spec, n)
                        )
            heads.append(part.gpu_experts)
            self.stats.partitions.append(
                {
                    "step": self.stats.steps,
                    "layer": li,
                    "n_gpu": len(part.gpu_experts),
                    "n_pim": len(part.pim_experts),
                    "t_total_est": part.t_total,
                }
            )
        return heads

    def _layer_telemetry(self, counts_per_layer: np.ndarray, heads) -> None:
        """Per-layer expert-token histograms and head-mass gauges of the
        step's sieve pass."""
        tel = self.tel
        for li, (counts, head) in enumerate(zip(counts_per_layer, heads)):
            while len(self._layer_metric_names) <= li:
                j = len(self._layer_metric_names)
                self._layer_metric_names.append(
                    (f"expert_tokens/layer{j}", f"head_mass/layer{j}")
                )
            hist_name, mass_name = self._layer_metric_names[li]
            routed = counts[counts > 0]
            total = int(routed.sum())
            tel.observe(hist_name, routed)
            if total > 0:
                # bimodality gauge: fraction of routed mass on the chosen
                # head (grouped-GEMM) set at this step's split
                gpu = np.asarray(head, dtype=np.int64)
                head_mass = float(counts[gpu].sum()) / total if gpu.size else 0.0
                tel.gauge(mass_name, head_mass)

    def _run_probes(self) -> None:
        """Refresh-cadence stage probes: measure the queued tail counts
        (the CostTable cells the split decides on) plus one head / dispatch
        / attention cell shaped like the last decode batch.  Off the
        critical path by construction — runs only at refresh boundaries."""
        moe = self.lm.arch.moe
        tails = sorted(self._pending_tail_counts)
        self._pending_tail_counts.clear()
        if len(tails) > _MAX_TAIL_PROBES:
            # sample evenly across the sorted counts so the probe budget
            # still covers the whole observed range
            idx = np.unique(
                np.linspace(0, len(tails) - 1, _MAX_TAIL_PROBES)
                .round()
                .astype(int)
            )
            tails = [tails[i] for i in idx]
        for n in tails:
            self._probes.tail(n)
        for _ in range(_SENTINEL_PROBES - tails.count(_SENTINEL_TAIL)):
            self._probes.tail(_SENTINEL_TAIL)
        if self._last_head_counts:
            self._probes.head(self._last_head_counts)
            self._last_head_counts = []
        if self._last_decode_batch:
            self._probes.dispatch(
                self._last_decode_batch, moe.n_held, moe.top_k
            )
            self._probes.attention(self._last_decode_batch, self._last_kv_depth)

    def _update_pim_health(self, step: int) -> None:
        """Boundary-cadence health pass over the measured cost loop.

        Two orthogonal detectors feed one gate:

        * **drift** — the sentinel tail cell's measured time vs the
          roofline model proxy.  The ratio is stationary while healthy
          (the EMA baseline absorbs the constant hardware/model scale),
          so a breach means the PIM-side stage genuinely slowed — the
          brownout signature;
        * **staleness** — the feed's accepted-poll counter.  A feed whose
          samples all fail validity/outlier filters stops advancing it
          even though no observation ever "looked wrong" — the poisoned-
          probe signature.

        Either flag quarantines the feed (model-proxy fallback) and
        clamps the next sieve export to GPU-only; clearance (with the
        monitor's hysteresis) re-warms the measured path.
        """
        mon, feed = self.health, self._timing_feed
        if mon is None or feed is None:
            return
        t = float(step)
        raw = feed.last_raw.get(_SENTINEL_TAIL)
        if raw is not None and self._roofline_t1 > 0:
            mon.observe("pim", raw / self._roofline_t1, t=t)
        mon.watch("cost_feed", float(feed.n_ok), t=t)
        healthy = mon.is_healthy("pim") and mon.is_healthy("cost_feed")
        if healthy != self.pim_healthy:
            self.pim_healthy = healthy
            feed.quarantined = not healthy
            if healthy:
                # accept the first measured window ungated: quarantine may
                # have re-seeded the table at the proxy's scale
                feed.rewarm()
        if self.tel.enabled:
            self.tel.gauge(
                "engine/pim_healthy", 1.0 if self.pim_healthy else 0.0
            )

    def step(self) -> List[Request]:
        """One engine step: admit -> prefill work -> decode -> retire."""
        t0 = time.perf_counter()
        tel = self.tel
        step_span = tel.span("engine/step", value=float(self.stats.steps))
        step_span.__enter__()
        with tel.span("engine/admit"):
            # queued requests past their service-start deadline leave
            # loudly before slot assignment — they never held KV
            expired = self.sched.expire_queue(t0)
            for r in expired:
                r.finish_time = t0
                self.sched.finished.append(r)
                self.stats.expired_requests += 1
            if expired and tel.enabled:
                tel.counter("engine/expired_requests", len(expired))
            self.sched.admit()

        # ---- prefill ----
        for req in self.sched.prefill_work():
            prompt = np.asarray(req.prompt, np.int32)[None, :]
            batch = {"tokens": jnp.asarray(prompt)}
            if self.paged is not None:
                # allocate the prompt's blocks up front; the scatter in
                # _prefill_chunk_impl writes through this block-table row
                self.paged.ensure(req.slot, len(req.prompt))
                batch["block_ids"] = jnp.asarray(
                    self.paged.block_table[req.slot]
                )
            if self.uses_cost_split:
                batch["sieve"] = self._sieve_state
            if self.lm.arch.family == "vlm":
                P = prompt.shape[1]
                pos = jnp.broadcast_to(jnp.arange(P), (1, P))
                batch["mrope_positions"] = jnp.stack([pos, pos, pos])
            with tel.span("engine/prefill", value=float(len(req.prompt))):
                out, self.cache, p_aux = self._prefill_chunk(
                    self.params, batch, self.cache, np.int32(req.slot)
                )
                picked = self._picked(out)
            if self.is_moe:
                self.stats.dropped_tokens += int(p_aux.dropped)
                self.stats.routed_tokens += int(np.asarray(p_aux.counts).sum())
            req.prefill_done = len(req.prompt)
            self.stats.prefill_tokens += len(req.prompt)
            tok = self._sample(picked)
            req.generated.append(int(tok[0]))
            if req.first_token_time is None:
                req.first_token_time = time.perf_counter()

        # ---- decode ----
        batch_reqs = self.sched.decode_batch()
        sieve_pass = None  # (counts, heads) for the step-end telemetry
        if batch_reqs:
            B = self.cfg.n_slots
            tokens = np.zeros((B, 1), np.int32)
            position = np.zeros((B,), np.int32)
            for r in batch_reqs:
                tokens[r.slot, 0] = (
                    r.generated[-1] if r.generated else r.prompt[-1]
                )
                # KV-write position of the token being fed: generated[-1]
                # was sampled but not yet written, so it lands one before
                # the request's next-write cursor.
                position[r.slot] = r.position - 1 if r.generated else r.position
            db = {"tokens": jnp.asarray(tokens), "position": jnp.asarray(position)}
            if self.paged is not None:
                # grow block lists to cover this step's KV write, then ship
                # the (fixed-shape) indexing state with the batch — same
                # jit signature every step, zero added cache misses
                for r in batch_reqs:
                    self.paged.ensure(r.slot, int(position[r.slot]) + 1)
                db["block_tables"] = jnp.asarray(self.paged.block_table)
                db["pool_owner"] = jnp.asarray(self.paged.owner)
                db["pool_pos"] = jnp.asarray(self.paged.block_pos)
            if self.uses_cost_split:
                db["sieve"] = self._sieve_state
            if self.lm.arch.family == "vlm":
                mp = jnp.asarray(position)[None, :, None]
                db["mrope_positions"] = jnp.concatenate([mp, mp, mp], axis=0)
            with tel.span("engine/decode", value=float(len(batch_reqs))):
                with tel.span("engine/decode_launch"):
                    out, self.cache, aux = self._decode(
                        self.params, db, self.cache
                    )
                if tel.enabled:
                    # the device's end, apart from the copy that follows
                    # (np.asarray makes the same sync untraced)
                    with tel.span("engine/decode_wait"):
                        jax.block_until_ready(out)
                with tel.span("engine/decode_logits"):
                    picked = self._picked(out)
            with tel.span("engine/decode_sample", value=float(picked.shape[0])):
                toks = self._sample(picked)
                for r in batch_reqs:
                    r.generated.append(int(toks[r.slot]))
                    self.stats.decode_tokens += 1
            tel.counter(
                "engine/device_sampled_rows",
                float(len(batch_reqs)) if self.greedy else 0.0,
            )
            if self.is_moe:
                with tel.span("engine/aux_to_host"):
                    dropped = int(aux.dropped)
                    counts = np.asarray(aux.counts)
                self.stats.dropped_tokens += dropped
                self.stats.routed_tokens += int(counts.sum())
            self._last_decode_batch = len(batch_reqs)
            self._last_kv_depth = int(position.max()) + 1
            if self.is_moe and counts.shape[0] > 0:
                # the scheduler sees the held experts' columns; the counters
                # count the assignments they take and the (layer, expert)
                # weight passes of the expert kernels: one per expert with
                # an assignment
                moe = self.lm.arch.moe
                held = counts[:, moe.held_offset:moe.held_offset + moe.n_held]
                tel.counter("engine/moe_held_assignments", float(held.sum()))
                tel.counter("engine/moe_expert_streams", float((held > 0).sum()))
                with tel.span("engine/sieve_host"):
                    heads = self._run_sieve(held)
                sieve_pass = (held, heads)

        # measured cost loop + cost-table refresh cadence: the in-graph
        # split only ever changes at these boundaries (stale-table
        # semantics between them)
        boundary = (self.stats.steps + 1) % self.sieve_refresh_every == 0
        if boundary and self._probes is not None:
            with tel.span("engine/probe"):
                self._run_probes()
                self._timing_feed.poll()
            self._update_pim_health(self.stats.steps + 1)
        if boundary and self.uses_cost_split:
            with tel.span("engine/sieve_refresh"):
                self._refresh_sieve_state(
                    step=self.stats.steps + 1,
                    gpu_only=not self.pim_healthy
                    or self.brownout_stage >= 2,
                )

        with tel.span("engine/retire"):
            # KV-capacity cap: the next decode feed writes KV at
            # ``r.position - 1``; once that reaches max_seq the dense
            # dynamic_update_slice would clamp and silently overwrite the
            # last entry (and the paged path would write past its last
            # block) — finish the request loudly instead.
            for r in self.sched.active:
                if (
                    r.generated
                    and not r.done
                    and r.position - 1 >= self.cfg.max_seq
                ):
                    r.truncated = True
                    self.stats.truncated_requests += 1

            done = self.sched.retire(time.perf_counter())
            if self.paged is not None:
                for r in done:
                    self.paged.free_slot(r.slot)
        # deadline-expired queue entries are terminal too — surface them
        # to the caller after the paged free loop (they never held a slot)
        done = expired + done
        self.stats.steps += 1
        self.stats.wall_time += time.perf_counter() - t0
        if tel.enabled:
            with tel.span("engine/telemetry"):
                if sieve_pass is not None:
                    self._layer_telemetry(*sieve_pass)
                # KV occupancy: fraction of the slot pool's cells holding
                # live KV entries (sum of per-request write cursors / cells)
                occ = sum(r.position for r in self.sched.active) / float(
                    self.cfg.n_slots * self.cfg.max_seq
                )
                tel.gauge("engine/kv_occupancy", occ)
                if self.paged is not None:
                    # fraction of allocatable pool blocks currently owned
                    tel.gauge(
                        "engine/kv_pool_used",
                        1.0 - self.paged.n_free / max(self.paged.n_pool - 1, 1),
                    )
                tel.gauge(
                    "engine/batch_occupancy",
                    len(batch_reqs) / max(self.cfg.n_slots, 1),
                )
                tel.gauge("engine/drop_rate", self.stats.drop_rate)
        step_span.__exit__(None, None, None)
        return done

    # ------------------------------------------------------------------
    def snapshot(
        self, snap_dir: str, snap_id: Optional[int] = None,
        keep: Optional[int] = None,
    ) -> str:
        """Atomic, checksummed snapshot of the engine's runtime state
        (KV cache + slots, SieveState, cost table, RNG, requests, feed and
        health monitors).  See :mod:`repro.recovery.snapshot`."""
        from repro.recovery.snapshot import save_engine_snapshot

        return save_engine_snapshot(self, snap_dir, snap_id=snap_id, keep=keep)

    def restore(self, snap_dir: str, snap_id: Optional[int] = None) -> int:
        """Restore from a snapshot (newest committed by default, walking
        back past corrupt ones); continues bit-identically — same tokens,
        same splits, zero added jit-cache misses (pinned by
        tests/test_recovery.py).  Returns the snap id restored."""
        from repro.recovery.snapshot import restore_engine_snapshot

        return restore_engine_snapshot(self, snap_dir, snap_id=snap_id)

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if self.sched.idle:
                break
            self.step()
        return self.sched.finished

    def _picked(self, out) -> np.ndarray:
        """The host copy that sampling reads from a compiled step's
        ``(ids, logits)``: the ``(B,)`` greedy ids the step picked, or,
        where the engine samples on the host, the ``(B, V)`` logits of the
        last position."""
        ids, logits = out
        return np.asarray(ids) if self.greedy else np.asarray(logits)[:, -1]

    def _sample(self, picked: np.ndarray) -> np.ndarray:
        """Each row's token from :meth:`_picked`'s copy: the step's own
        greedy ids, or a temperature-1 draw from the logits with the
        engine's RNG (in float32: bfloat16 probabilities do not sum to 1
        closely enough for ``choice``)."""
        if self.greedy:
            return picked
        logits = picked.astype(np.float32)
        z = logits - logits.max(-1, keepdims=True)
        p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
        return np.array(
            [self.rng.choice(p.shape[-1], p=p[i]) for i in range(p.shape[0])]
        )
