"""Mixture-of-Experts layer: router, capacity dispatch, EP, Sieve dual-path.

Design (DESIGN.md §5, §8.2):

* **Router**: fp32 logits, softmax scores, top-k, GShard-style
  load-balancing aux loss.  Weights are the top-k scores renormalised to
  sum 1 (``norm_topk_prob``, Qwen3-MoE) or, as DeepSeek-V2 publishes them,
  left unnormalised and multiplied by ``routed_scaling_factor``; with
  ``n_group > 1`` the top-k come from each token's ``topk_group`` best
  expert groups (DeepSeek-V2's ``group_limited_greedy``).
* **Held share**: a layer may hold experts [held_offset, held_offset +
  n_held) of its router's n_experts, one chip's part of an expert-parallel
  group run without the exchange: it routes over all of them, dispatches
  only the assignments its experts take, and returns that partial output.
* **Dispatch**: capacity-based scatter (sort-free, one-hot-free) into an
  ``(E, C, d)`` buffer — static SPMD shapes, no fake matmul FLOPs, matches
  the paper's fixed-size-tensor metadata step (§6.1 ④).  Overflow tokens
  are dropped and counted.
* **EP**: experts sharded over the ``model`` mesh axis; dispatch/combine via
  ``jax.lax.all_to_all`` inside ``shard_map`` (the paper's ⑤/⑨ a2a steps).
* **Sieve integration**: per-layer expert token counts are computed in-graph
  and exposed to the serving engine (which feeds the EMA cost table and the
  Sieve scheduler).  ``expert_exec="dual_path"`` routes 1-few-token
  ("tail") experts through a streaming SwiGLU GEMV and popular ("head")
  experts through a grouped SwiGLU GEMM (both in kernels/fused_swiglu,
  with XLA twins off the TPU) — the TPU adaptation of the paper's PIM/GPU
  split (DESIGN.md §2).  The split is computed in-graph from the routed counts
  (:func:`repro.core.scheduler_jax.dual_path_split`): counts-driven, no
  host sync on the decode critical path.  ``expert_exec="dense"`` keeps the
  one-einsum capacity path as the bit-level reference oracle.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.core.scheduler_jax import (
    SieveState,
    dual_path_split,
    dual_path_split_cost,
    make_sieve_state,
)
from .layers import _he

from jax.sharding import PartitionSpec as P


def _shard_map(f, *, mesh, in_specs, out_specs):
    # the EP bodies do manual psums the replication checker cannot verify
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


class MeshInfo(NamedTuple):
    """How model code should distribute itself (None = single-device)."""

    mesh: Optional[object]  # jax.sharding.Mesh
    data_axes: Tuple[str, ...]  # mesh axes sharding the batch ("pod","data")
    model_axis: Optional[str]  # mesh axis for TP/EP

    @property
    def ep_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


LOCAL_MESH = MeshInfo(None, (), None)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_moe(key, arch: ArchConfig, dtype=jnp.bfloat16) -> dict:
    cfg = arch.moe
    d, f, E = arch.d_model, cfg.d_expert, cfg.n_held
    ks = jax.random.split(key, 5)
    p = {
        "w_router": (
            jax.random.normal(ks[0], (d, cfg.n_experts)) * 0.02
        ).astype(jnp.float32),
        "w_gate": _he(ks[1], (E, d, f), 1.0, dtype),
        "w_up": _he(ks[2], (E, d, f), 1.0, dtype),
        "w_down": _he(ks[3], (E, f, d), 1.0, dtype),
    }
    if cfg.n_shared:
        sks = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _he(sks[0], (d, cfg.n_shared * f), 1.0, dtype),
            "w_up": _he(sks[1], (d, cfg.n_shared * f), 1.0, dtype),
            "w_down": _he(sks[2], (cfg.n_shared * f, d), 1.0, dtype),
        }
        if cfg.shared_gate:
            p["shared"]["w_expert_gate"] = _he(
                jax.random.fold_in(ks[4], 1), (d, 1), 1.0, dtype)
    return p


def moe_param_pspecs(arch: ArchConfig, model_axis: str) -> dict:
    """PartitionSpecs matching init_moe: experts sharded over the model axis
    (EP), shared experts tensor-parallel over the same axis."""
    cfg = arch.moe
    p = {
        "w_router": P(None, None),
        "w_gate": P(model_axis, None, None),
        "w_up": P(model_axis, None, None),
        "w_down": P(model_axis, None, None),
    }
    if cfg.n_shared:
        p["shared"] = {
            "w_gate": P(None, model_axis),
            "w_up": P(None, model_axis),
            "w_down": P(model_axis, None),
        }
        if cfg.shared_gate:
            p["shared"]["w_expert_gate"] = P(None, None)
    return p


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------


class RouterOut(NamedTuple):
    expert_idx: jax.Array  # (T, k) int32
    weights: jax.Array  # (T, k) activation dtype
    aux_loss: jax.Array  # scalar fp32
    counts: jax.Array  # (E,) int32 token count per expert


def _group_limited(probs: jax.Array, cfg: MoEConfig) -> jax.Array:
    """The scores with every expert outside a token's ``topk_group`` best
    groups set to 0; a group scores its best expert."""
    T, E = probs.shape
    G = cfg.n_group
    with jax.named_scope("moe/group_route"):
        group_score = probs.reshape(T, G, E // G).max(-1)  # (T, G)
        _, gi = jax.lax.top_k(group_score, cfg.topk_group)
        keep = jax.nn.one_hot(gi, G, dtype=jnp.int32).sum(1) > 0  # (T, G)
        return jnp.where(jnp.repeat(keep, E // G, axis=1), probs, 0.0)


@jax.named_scope("moe/router")
def route(x: jax.Array, w_router: jax.Array, cfg: MoEConfig) -> RouterOut:
    """Top-k routing (group-limited where ``cfg.n_group > 1``), weights
    renormalised or scaled as ``cfg`` says, plus load-balance aux loss."""
    T = x.shape[0]
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs if cfg.n_group == 1 else _group_limited(probs, cfg)
    top_p, top_i = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    else:
        weights = top_p * cfg.routed_scaling_factor
    # GShard aux loss: E * sum_e mean_t(prob_e) * mean_t(frac_routed_e)
    E = w_router.shape[1]
    frac = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (
        T * cfg.top_k
    )
    aux = E * jnp.sum(probs.mean(0) * frac)
    counts = jnp.zeros((E,), jnp.int32).at[top_i.reshape(-1)].add(1)
    return RouterOut(top_i.astype(jnp.int32), weights.astype(x.dtype), aux, counts)


# ---------------------------------------------------------------------------
# Capacity-based dispatch / combine (scatter, no one-hot matmuls)
# ---------------------------------------------------------------------------


class Dispatched(NamedTuple):
    buf: jax.Array  # (E, C, d)
    slot_of: jax.Array  # (T, k) int32: slot in flat (E*C) space, -1 if dropped
    n_dropped: jax.Array  # scalar int32


def capacity(T: int, cfg: MoEConfig, n_experts: int) -> int:
    c = int(-(-T * cfg.top_k * cfg.capacity_factor // n_experts))
    return max(c, min(T, cfg.min_capacity), 1)


# Counting-scatter dispatch does Theta(Tk * nE) work/memory for its
# running-counter cumsum; the stable argsort it replaces is
# O(Tk log Tk).  The crossover was set from CPU timings at E=128, k=8,
# before the program ran on a chip: around Tk*(nE+1) ~ 4M elements (a
# ~16 MB int32 intermediate), below which the counters won and above
# which the sort did.  No benchmark cell reaches it (qwen3's 512-token
# prefill is 0.53M elements), so on the chip it is unmeasured.  Both
# formulations are bit-identical, so the switch is purely a cost choice
# made at trace time (shapes are static under jit).
_COUNTING_DISPATCH_MAX_ELEMS = 4_000_000


@jax.named_scope("moe/dispatch")
def dispatch(
    x: jax.Array,  # (T, d)
    r: RouterOut,
    n_experts: int,
    cap: int,
    expert_offset: int = 0,
    n_local: Optional[int] = None,
) -> Dispatched:
    """Scatter tokens into an (n_local, cap, d) buffer — sort-free on the
    decode hot path.

    An assignment's capacity slot is its *rank* among same-expert
    assignments in token order.  The ranks come from a counting scatter —
    running per-expert counters over the flattened (T*k) assignment
    stream (a cumulative sum of the expert one-hots) — instead of the
    stable ``argsort`` the original dispatch used, removing the
    O(Tk log Tk) sort from every MoE layer of every decode step.  Token
    order is what the stable sort preserved within each expert, so the
    ranks (and with them ``buf``, ``slot_of`` and ``n_dropped``) are
    bit-identical to the argsort formulation (pinned by
    tests/test_fused_swiglu.py against :func:`dispatch_argsort`, which
    also remains the executor for prefill-scale batches where the
    counting matrix would outgrow the sort — see
    ``_COUNTING_DISPATCH_MAX_ELEMS``).

    With ``expert_offset``/``n_local`` set, only assignments targeting the
    local expert shard [offset, offset + n_local) are dispatched (the
    expert-parallel case); others are masked out (their slot_of is -1 and
    they contribute nothing — a remote shard handles them).
    """
    T = x.shape[0]
    k = r.expert_idx.shape[1]
    nE = n_experts if n_local is None else n_local
    if T * k * (nE + 1) > _COUNTING_DISPATCH_MAX_ELEMS:
        return dispatch_argsort(
            x, r, n_experts, cap, expert_offset=expert_offset, n_local=n_local
        )
    return dispatch_counting(
        x, r, n_experts, cap, expert_offset=expert_offset, n_local=n_local
    )


def dispatch_counting(
    x: jax.Array,  # (T, d)
    r: RouterOut,
    n_experts: int,
    cap: int,
    expert_offset: int = 0,
    n_local: Optional[int] = None,
) -> Dispatched:
    """The counting-scatter formulation itself (no size fallback) — what
    :func:`dispatch` runs below the crossover; exposed so benchmarks and
    tests can measure/pin it at any size."""
    T, d = x.shape
    k = r.expert_idx.shape[1]
    Tk = T * k
    nE = n_experts if n_local is None else n_local
    e_flat = r.expert_idx.reshape(-1) - expert_offset
    valid = (e_flat >= 0) & (e_flat < nE)
    e_key = jnp.where(valid, e_flat, nE).astype(jnp.int32)
    # counting scatter: pos[i] = #{j < i : e_key[j] == e_key[i]} — the
    # running per-expert counter read just before assignment i bumps it
    onehot = e_key[:, None] == jnp.arange(nE + 1, dtype=jnp.int32)[None, :]
    running = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    pos = jnp.take_along_axis(running, e_key[:, None], axis=1)[:, 0]
    keep = (pos < cap) & valid
    slot = jnp.where(keep, e_key * cap + pos, nE * cap)
    token_of = jnp.arange(Tk, dtype=jnp.int32) // k
    vals = x[token_of] * keep[:, None].astype(x.dtype)
    buf = (
        jnp.zeros((nE * cap + 1, d), x.dtype)
        .at[slot].set(vals)[: nE * cap]
        .reshape(nE, cap, d)
    )
    slot_of = jnp.where(keep, slot, -1).reshape(T, k)
    n_dropped = jnp.sum(
        (~keep) & valid
    ).astype(jnp.int32)  # overflow only (not remote assignments)
    return Dispatched(buf, slot_of, n_dropped)


def dispatch_argsort(
    x: jax.Array,  # (T, d)
    r: RouterOut,
    n_experts: int,
    cap: int,
    expert_offset: int = 0,
    n_local: Optional[int] = None,
) -> Dispatched:
    """Stable-argsort dispatch (the original formulation) — kept as the
    reference oracle for the sort-free :func:`dispatch`."""
    T, d = x.shape
    k = r.expert_idx.shape[1]
    Tk = T * k
    nE = n_experts if n_local is None else n_local
    e_flat = r.expert_idx.reshape(-1) - expert_offset
    valid = (e_flat >= 0) & (e_flat < nE)
    e_key = jnp.where(valid, e_flat, nE)  # invalid sort to the end
    order = jnp.argsort(e_key, stable=True)
    e_sorted = e_key[order]
    counts = jnp.zeros((nE + 1,), jnp.int32).at[e_key].add(1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(Tk, dtype=jnp.int32) - starts[e_sorted]
    keep = (pos_sorted < cap) & (e_sorted < nE)
    slot_sorted = jnp.where(keep, e_sorted * cap + pos_sorted, nE * cap)
    # back to (T, k) order
    slot_flat = jnp.zeros((Tk,), jnp.int32).at[order].set(slot_sorted)
    token_sorted = order // k
    vals = x[token_sorted] * keep[:, None].astype(x.dtype)
    buf = (
        jnp.zeros((nE * cap + 1, d), x.dtype)
        .at[slot_sorted].set(vals)[: nE * cap]
        .reshape(nE, cap, d)
    )
    slot_of = jnp.where(slot_flat == nE * cap, -1, slot_flat).reshape(T, k)
    n_dropped = jnp.sum(
        (~keep) & (e_sorted < nE)
    ).astype(jnp.int32)  # overflow only (not remote assignments)
    return Dispatched(buf, slot_of, n_dropped)


@jax.named_scope("moe/combine")
def combine(
    y_buf: jax.Array,  # (E, C, d)
    slot_of: jax.Array,  # (T, k)
    weights: jax.Array,  # (T, k)
    T: int,
) -> jax.Array:
    E, C, d = y_buf.shape
    flat = y_buf.reshape(E * C, d)
    idx = jnp.maximum(slot_of, 0)
    gathered = flat[idx.reshape(-1)].reshape(T, -1, d)
    mask = (slot_of >= 0)[..., None].astype(flat.dtype)
    w = weights[..., None].astype(flat.dtype)
    return jnp.sum(gathered * mask * w, axis=1)


# ---------------------------------------------------------------------------
# Expert FFN compute: dense oracle + sieve dual-path executor
# ---------------------------------------------------------------------------


def experts_ffn(params: dict, buf: jax.Array) -> jax.Array:
    """SwiGLU over (E_local, C_total, d) with (E_local, d, f) weights.

    The dense reference oracle: every capacity slot — live or padding —
    pays full FLOPs.  ``experts_ffn_dual`` is the runtime sieve split that
    skips the dead work.
    """
    gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


# ---------------------------------------------------------------------------
# Sieve cost-model state for the cost-driven split
# ---------------------------------------------------------------------------

# Default table depth: counts beyond it clamp to the last entry inside the
# split, so the default only needs to cover decode/prefill-sized batches.
_DEFAULT_SIEVE_MAX_COUNT = 2048


@functools.lru_cache(maxsize=16)
def _default_sieve_state(
    d_model: int, d_expert: int, n_experts: int, top_k: int, n_shared: int,
    max_count: int,
) -> SieveState:
    from repro.core.cost_model import CostModel, MoELayerSpec, b200_pim_system

    cm = CostModel(
        system=b200_pim_system(),
        layer=MoELayerSpec(
            d_model=d_model, d_ff=d_expert, n_experts=n_experts,
            top_k=top_k, n_shared=n_shared,
        ),
    )
    return make_sieve_state(None, cm, max_count)


def default_sieve_state(
    arch: ArchConfig, max_count: int = _DEFAULT_SIEVE_MAX_COUNT
) -> SieveState:
    """Roofline-only :class:`SieveState` for the arch's MoE layer dims.

    The fallback when no engine-exported state is provided (training,
    standalone tests, dry runs): the nominal PIM roofline of the default
    paper system, with no measured observations.  The serving engine
    replaces it with the live EMA table on its refresh cadence.
    """
    cfg = arch.moe
    return _default_sieve_state(
        arch.d_model, cfg.d_expert, cfg.n_held, cfg.top_k, cfg.n_shared,
        max_count,
    )


def resolve_sieve_state(
    cfg: MoEConfig, d_model: int, sieve: Optional[SieveState]
) -> Optional[SieveState]:
    """The cost state actually used by the executor: the caller-provided
    state under ``expert_exec="dual_path_cost"`` (defaulting to the
    roofline state), ``None`` for the cost-blind modes."""
    if cfg.expert_exec != "dual_path_cost":
        return None
    if sieve is not None:
        return sieve
    return _default_sieve_state(
        d_model, cfg.d_expert, cfg.n_held, cfg.top_k, cfg.n_shared,
        _DEFAULT_SIEVE_MAX_COUNT,
    )


def _dual_backend() -> str:
    """Kernel backend for the dual path: Pallas on TPU, XLA ragged ops on
    CPU/GPU hosts (where interpret-mode Pallas would be pure overhead).
    ``REPRO_DUAL_BACKEND=pallas|xla`` overrides (tests force ``pallas`` to
    make the kernels load-bearing under interpret mode)."""
    env = os.environ.get("REPRO_DUAL_BACKEND")
    if env in ("pallas", "xla"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _swiglu_grouped_xla(slab, wg, wu, wd, sizes, rhs_of_group=None):
    """XLA twin of the grouped head path (einsum + live-row mask)."""
    if rhs_of_group is not None:
        wg, wu, wd = wg[rhs_of_group], wu[rhs_of_group], wd[rhs_of_group]
    gate = jnp.einsum("gcd,gdf->gcf", slab, wg)
    up = jnp.einsum("gcd,gdf->gcf", slab, wu)
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("gcf,gfd->gcd", h, wd)
    live = (
        jnp.arange(slab.shape[1], dtype=jnp.int32)[None, :] < sizes[:, None]
    )
    return y * live[..., None].astype(y.dtype)


def _tail_path(slab, wg, wu, wd, e_of_g, valid, backend, gather_w: bool):
    """Shared tail executor over the (G, tau, d) per-group slab.

    ``valid`` is the (G, tau) live-row mask; ``gather_w`` is False when
    groups already align 1:1 with the weight rows (plain layout, where an
    identity gather would only copy the weights)."""
    G, tau, d = slab.shape
    if backend == "pallas":
        # imported where a Pallas path is traced, not with this module: at
        # module level it added 4-6 s to start-up on a TPU v5e host
        from repro.kernels import ops as kernel_ops

        toks = slab.reshape(G * tau, d)
        eids = jnp.repeat(e_of_g, tau)
        ty = kernel_ops.swiglu_gemv(
            toks, wg, wu, wd, eids, valid.reshape(G * tau).astype(jnp.int32)
        )
        return ty.reshape(G, tau, d)
    if gather_w:
        wg, wu, wd = wg[e_of_g], wu[e_of_g], wd[e_of_g]
    tg = jnp.einsum("gtd,gdf->gtf", slab, wg)
    tu = jnp.einsum("gtd,gdf->gtf", slab, wu)
    th = jax.nn.silu(tg) * tu
    ty = jnp.einsum("gtf,gfd->gtd", th, wd)
    return ty * valid[..., None].astype(ty.dtype)


# ---------------------------------------------------------------------------
# Named stage boundaries (telemetry probe hooks)
# ---------------------------------------------------------------------------
#
# The dual-path decode step fuses its stages inside one compiled function,
# so per-stage wall times cannot be read from the hot path directly.  These
# public stage entry points expose the exact stage code — same backend
# selection, same kernels — so ``repro.telemetry.probes.StageProbes`` can
# execute each stage standalone ("timed decode-step cells") on the engine's
# EMA refresh cadence and record *measured* stage durations as spans.


def tail_stage(toks, wg, wu, wd, eids, valid, backend: Optional[str] = None):
    """Tail-path stage boundary: per-row streaming expert SwiGLU.

    ``toks`` is (S, d); each row streams its expert's three weight
    matrices once (the PIM-GEMV proxy).  Pallas fused-GEMV kernel on TPU,
    per-row gathered einsum twin elsewhere — the same selection
    :func:`experts_ffn_dual` makes for its tail.
    """
    if backend is None:
        backend = _dual_backend()
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops

        return kernel_ops.swiglu_gemv(toks, wg, wu, wd, eids, valid)
    we_g, we_u, we_d = wg[eids], wu[eids], wd[eids]
    g = jnp.einsum("td,tdf->tf", toks, we_g)
    u = jnp.einsum("td,tdf->tf", toks, we_u)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tf,tfd->td", h, we_d)
    if valid is not None:
        y = y * valid.astype(y.dtype)[:, None]
    return y


def head_stage(slab, wg, wu, wd, sizes, backend: Optional[str] = None):
    """Head-path stage boundary: grouped SwiGLU over capacity slabs.

    ``slab`` is (G, C, d) with ``sizes`` live rows per group — the
    compacted hot-expert slab the grouped path executes.  Fused Pallas
    kernel on TPU, XLA einsum twin elsewhere.
    """
    if backend is None:
        backend = _dual_backend()
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops

        return kernel_ops.swiglu_gmm_capacity(slab, wg, wu, wd, sizes)
    return _swiglu_grouped_xla(slab, wg, wu, wd, sizes)


def _dual_split(
    rows: jax.Array,
    cfg: MoEConfig,
    tau: int,
    max_head: Optional[int],
    sieve: Optional[SieveState],
    weight_of_group: Optional[jax.Array] = None,
) -> dict:
    """Head/tail split for the dual executor: the fixed threshold rule
    (``dual_path``) or the cost-driven rule (``dual_path_cost``) over the
    provided :class:`SieveState`.  Both are traceable with no host sync."""
    if cfg.expert_exec == "dual_path_cost":
        if sieve is None:
            raise ValueError(
                "expert_exec='dual_path_cost' needs a SieveState; resolve "
                "one via resolve_sieve_state()/default_sieve_state()"
            )
        return dual_path_split_cost(
            rows, sieve.pim_time_by_count, sieve.params,
            tail_tokens=tau, max_head=max_head,
            weight_of_group=weight_of_group,
        )
    return dual_path_split(rows, tail_tokens=tau, max_head=max_head)


def experts_ffn_dual(
    params: dict,
    buf: jax.Array,  # (E, C, d) capacity dispatch buffer
    rows: jax.Array,  # (E,) live rows per expert (routed count clipped at C)
    cfg: MoEConfig,
    backend: Optional[str] = None,
    sieve: Optional[SieveState] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Runtime sieve-split dual-path expert execution.

    Splits the experts on the in-graph prefix rule: experts with more than
    ``cfg.dual_tail_tokens`` buffered rows form the *head* and run as one
    grouped SwiGLU over their capacity slabs (compacted to the
    ``cfg.dual_max_head`` most popular experts when a budget is set); the
    remaining *tail* experts stream their rows through the SwiGLU GEMV
    kernel.  Under ``expert_exec="dual_path"`` the boundary is the fixed
    threshold (:func:`dual_path_split`); under ``"dual_path_cost"`` it is
    the cost-model argmin over the ``sieve`` state
    (:func:`dual_path_split_cost`) — the same prefix family, so the
    executor below is shared.  Head and tail cover disjoint buffer rows,
    so the merge is one add.  Returns ``(y_buf, n_exec_dropped)`` where
    the drop count is nonzero only when a head budget squeezes a
    >tau-row expert off the grouped path (0 with the default
    ``dual_max_head=0``).
    With ``params["expert_base"]`` set (a traced int32 ``l*E``), the
    weights are the whole layer-stacked arrays viewed as ``(L*E, d, f)``
    / ``(L*E, f, d)``: the kernels read expert ``e`` of layer ``l`` at row
    ``l*E + e`` through their scalar-prefetch tables (Pallas backend only;
    see :func:`experts_in_place`).
    """
    if backend is None:
        backend = _dual_backend()
    E, C, d = buf.shape
    tau = int(min(max(cfg.dual_tail_tokens, 0), C))
    H = cfg.dual_max_head if 0 < cfg.dual_max_head < E else E
    with jax.named_scope("moe/dispatch"):
        split = _dual_split(rows, cfg, tau, (H if H < E else None), sieve)
    head_sizes_full = jnp.where(split["head_mask"], rows, 0).astype(jnp.int32)

    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    # weight row of each expert: its own index, or l*E + e where the
    # weights are the whole layer-stacked (L*E, ...) arrays
    base = params.get("expert_base")
    if base is not None and backend != "pallas":
        raise ValueError("layer-stacked expert weights need the Pallas kernels")
    w_row = jnp.arange(E, dtype=jnp.int32)
    if base is not None:
        w_row = base + w_row
    with jax.named_scope("moe/head"):
        if H < E:
            # compact: gather the H most popular experts' slabs; each keeps
            # its weight row through the rhs_of_group table
            hid = split["order"][:H]
            slab = buf[hid]
            head_sizes = head_sizes_full[hid]
            rhs = w_row[hid]
        else:
            slab, head_sizes, rhs = buf, head_sizes_full, None

        if backend == "pallas":
            from repro.kernels import ops as kernel_ops

            y_head = kernel_ops.swiglu_gmm_capacity(
                slab, wg, wu, wd, head_sizes,
                rhs_of_group=w_row if rhs is None else rhs,
            )
        else:
            y_head = _swiglu_grouped_xla(
                slab, wg, wu, wd, head_sizes, rhs_of_group=rhs
            )
        if H < E:
            y = jnp.zeros((E, C, d), y_head.dtype).at[hid].set(y_head)
        else:
            y = y_head

    if tau > 0:
        with jax.named_scope("moe/tail"):
            # tail slab: every expert's first tau capacity rows; rows of
            # head experts / beyond the live count are masked invalid.
            live = jnp.arange(tau, dtype=jnp.int32)[None, :] < jnp.minimum(
                rows, tau
            )[:, None]
            valid = split["tail_mask"][:, None] & live
            ty = _tail_path(
                buf[:, :tau, :], wg, wu, wd, w_row, valid, backend,
                gather_w=False,
            )
            y = y.at[:, :tau, :].add(ty.astype(y.dtype))

    return y.astype(buf.dtype), split["n_dropped"]


def experts_ffn_dual_segmented(
    params: dict,
    buf: jax.Array,  # (E, S, C, d): S ragged segments per local expert
    sizes: jax.Array,  # (E, S) live rows per (expert, segment)
    cfg: MoEConfig,
    backend: Optional[str] = None,
    sieve: Optional[SieveState] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Dual-path execution over the EP a2a layout.

    After the dispatch all_to_all each local expert's rows arrive as one
    capacity segment per source shard; every (expert, segment) pair is its
    own ragged group (a hot expert's 1-token segment from a quiet shard
    still takes the GEMV path).  Groups share their expert's weights via
    the kernel's ``rhs_of_group`` table — no weight replication.

    ``cfg.dual_max_head`` is honored per segment: the budget H (an
    expert-equivalent count, so H*S segments) compacts the grouped path to
    the most popular (expert, source-shard) segments — gathered with their
    ``rhs_of_group`` weight rows, no host sync — and rows squeezed past
    both the budget and the tail slab are dropped and counted, the same
    contract as :func:`experts_ffn_dual`.  Returns
    ``(y_buf, n_exec_dropped)``.
    """
    if backend is None:
        backend = _dual_backend()
    E, S, C, d = buf.shape
    G = E * S
    tau = int(min(max(cfg.dual_tail_tokens, 0), C))
    # head budget in segment units: H experts' worth of capacity slabs
    Hg = cfg.dual_max_head * S if 0 < cfg.dual_max_head * S < G else G
    rows_g = sizes.reshape(G).astype(jnp.int32)
    e_of_g = jnp.repeat(jnp.arange(E, dtype=jnp.int32), S)
    # an expert's weights are shared across its segments: only its most
    # popular segment (the first to enter any prefix) charges the weight
    # bytes in the cost-driven split's T_GPU term
    first_seg = (
        jnp.zeros((E, S), jnp.int32)
        .at[jnp.arange(E), jnp.argmax(sizes, axis=1)]
        .set(1)
        .reshape(G)
    )
    with jax.named_scope("moe/dispatch"):
        split = _dual_split(
            rows_g, cfg, tau, (Hg if Hg < G else None), sieve,
            weight_of_group=first_seg,
        )
    head_sizes_full = jnp.where(split["head_mask"], rows_g, 0).astype(jnp.int32)

    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    slab_full = buf.reshape(G, C, d)
    with jax.named_scope("moe/head"):
        if Hg < G:
            # compact: gather the Hg most popular segments' slabs; each
            # keeps its expert's weight row through the rhs_of_group table
            hid = split["order"][:Hg]
            slab = slab_full[hid]
            head_sizes = head_sizes_full[hid]
            rhs = e_of_g[hid]
        else:
            slab, head_sizes, rhs = slab_full, head_sizes_full, e_of_g

        if backend == "pallas":
            from repro.kernels import ops as kernel_ops

            y_head = kernel_ops.swiglu_gmm_capacity(
                slab, wg, wu, wd, head_sizes, rhs_of_group=rhs
            )
        else:
            y_head = _swiglu_grouped_xla(
                slab, wg, wu, wd, head_sizes, rhs_of_group=rhs
            )
        if Hg < G:
            y = jnp.zeros((G, C, d), y_head.dtype).at[hid].set(y_head)
        else:
            y = y_head

    if tau > 0:
        with jax.named_scope("moe/tail"):
            live = jnp.arange(tau, dtype=jnp.int32)[None, :] < jnp.minimum(
                rows_g, tau
            )[:, None]
            valid = split["tail_mask"][:, None] & live
            ty = _tail_path(
                slab_full[:, :tau, :], wg, wu, wd, e_of_g, valid, backend,
                gather_w=True,
            )
            y = y.at[:, :tau, :].add(ty.astype(y.dtype))
    return (
        y.reshape(E, S, C, d).astype(buf.dtype),
        split["n_dropped"],
    )


_EXEC_MODES = ("dense", "dual_path", "dual_path_cost")
_DUAL_MODES = ("dual_path", "dual_path_cost")
ROUTED_STACKS = ("w_gate", "w_up", "w_down")


def experts_in_place(arch: ArchConfig, mi: MeshInfo) -> bool:
    """Whether an inference walk hands the expert kernels each routed
    weight stack whole, ``(L, E, ...)`` viewed as ``(L*E, ...)``, with the
    layer's base row ``l*E`` in ``params["expert_base"]``, in place of the
    layer's slice.  The slice feeds a Pallas call, so XLA would copy it
    (one layer's three stacks per layer and step); it is kept where that
    copy does not arise or the view does not hold: the XLA backend fuses
    the slice into its einsums, the EP bodies hold stacks sharded on the
    expert axis, and ``expert_exec="dense"`` runs no kernel."""
    return (
        arch.moe is not None
        and arch.moe.expert_exec in _DUAL_MODES
        and mi.ep_size <= 1
        and _dual_backend() == "pallas"
    )


def _check_expert_exec(cfg: MoEConfig) -> None:
    if cfg.expert_exec not in _EXEC_MODES:
        raise ValueError(
            f"unknown MoEConfig.expert_exec {cfg.expert_exec!r}; "
            f"expected one of {_EXEC_MODES}"
        )


def experts_ffn_exec(
    params: dict,
    buf: jax.Array,  # (E, C, d)
    rows: jax.Array,  # (E,) live rows per expert
    cfg: MoEConfig,
    sieve: Optional[SieveState] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch on ``cfg.expert_exec``; returns (y_buf, n_exec_dropped)."""
    _check_expert_exec(cfg)
    if cfg.expert_exec in _DUAL_MODES:
        sieve = resolve_sieve_state(cfg, buf.shape[-1], sieve)
        return experts_ffn_dual(params, buf, rows, cfg, sieve=sieve)
    return experts_ffn(params, buf), jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# MoE layer: local and expert-parallel paths
# ---------------------------------------------------------------------------


class MoEOut(NamedTuple):
    y: jax.Array  # (T, d)
    aux_loss: jax.Array
    # (E,) token counts per router output (Sieve scheduler input): global
    # under EP; on a held share, the assignments its experts take (zero
    # off the share)
    counts: jax.Array
    n_dropped: jax.Array


def moe_local(
    params: dict,
    x: jax.Array,
    arch: ArchConfig,
    sieve: Optional[SieveState] = None,
) -> MoEOut:
    """Single-device routed-experts path (reference; also the per-shard math
    when EP is disabled).  On a held share (``cfg.n_held < cfg.n_experts``)
    only the assignments to the held experts are dispatched and computed:
    ``y`` is their part of the layer's routed output."""
    cfg = arch.moe
    T = x.shape[0]
    r = route(x, params["w_router"], cfg)
    cap = capacity(T, cfg, cfg.n_experts)
    off, n = cfg.held_offset, cfg.n_held
    disp = dispatch(x, r, cfg.n_experts, cap, expert_offset=off, n_local=n)
    e = jnp.arange(cfg.n_experts)
    counts = jnp.where((e >= off) & (e < off + n), r.counts, 0)
    rows = jnp.minimum(counts[off:off + n], cap)
    y_buf, exec_dropped = experts_ffn_exec(params, disp.buf, rows, cfg, sieve)
    y = combine(y_buf, disp.slot_of, r.weights, T)
    return MoEOut(y, r.aux_loss, counts, disp.n_dropped + exec_dropped)


def _ep_body(
    params: dict,
    x: jax.Array,
    arch: ArchConfig,
    mi: MeshInfo,
    sieve: Optional[SieveState] = None,
) -> MoEOut:
    """Per-shard EP body (runs inside shard_map).

    x: (T_ds, d) — this *data shard's* tokens, replicated over the model
    axis.  Expert weights: (E_local, d, f) — this model shard's experts.

    Execution maps the paper's Fig-8 flow onto TPU collectives: the router
    ② runs redundantly on every model shard (cheap — it IS the routing-map
    AllGather ③: afterwards every shard knows the full token→expert map);
    each shard dispatches ④ only the tokens routed to *its* experts (the
    paper's ⑤ dispatch, with the token movement folded into the final
    combine), computes its experts' FFNs ⑦, and the partial outputs are
    summed over the model axis ⑨/⑩ (each token's k experts live on k ≤ nm
    different shards, so the psum is exactly the paper's aggregation).

    This "replicated-dispatch EP" works for every batch size including
    single-token decode (no divisibility constraints between tokens and the
    EP degree); the a2a-dispatch variant is a §Perf alternative for large
    training batches.
    """
    cfg = arch.moe
    axis = mi.model_axis
    nm = mi.ep_size
    E = cfg.n_experts
    E_loc = E // nm
    T, d = x.shape

    r = route(x, params["w_router"], cfg)
    cap = capacity(T, cfg, E)
    shard = jax.lax.axis_index(axis)
    disp = dispatch(x, r, E, cap, expert_offset=shard * E_loc, n_local=E_loc)

    # (E_loc,) rows actually in this shard's buffer: the local slice of the
    # global routed counts, clipped at capacity.
    local_rows = jnp.minimum(
        jax.lax.dynamic_slice(r.counts, (shard * E_loc,), (E_loc,)), cap
    )
    y_buf, exec_dropped = experts_ffn_exec(
        params, disp.buf, local_rows, cfg, sieve
    )
    y_partial = combine(y_buf, disp.slot_of, r.weights, T)
    y = jax.lax.psum(y_partial, axis)

    # Global token counts per expert (the Sieve scheduler's input ③): the
    # router saw this data shard's tokens; sum over the data axes.
    counts = r.counts
    aux = r.aux_loss
    dropped = jax.lax.psum(disp.n_dropped + exec_dropped, axis)
    if mi.data_axes:
        counts = jax.lax.psum(counts, mi.data_axes)
        aux = jax.lax.pmean(aux, mi.data_axes)
        dropped = jax.lax.psum(dropped, mi.data_axes)
    return MoEOut(y, aux, counts, dropped)


def _ep_a2a_body(
    params: dict,
    x: jax.Array,
    arch: ArchConfig,
    mi: MeshInfo,
    sieve: Optional[SieveState] = None,
) -> MoEOut:
    """all-to-all-dispatch EP (§Perf B future-work lever, REPRO_EP_MODE=a2a).

    Tokens are sharded over (data x model) — each shard routes its own
    tokens, scatters them into a full-E capacity buffer, and exchanges
    buffers with the expert-owning shards via two all_to_alls (the paper's
    ⑤ dispatch / ⑨ combine).  Communication moves ~k/TP of the activations
    instead of the full d_model psum of the replicated-dispatch path —
    cheaper for large training batches; requires tokens divisible by the
    full mesh.
    """
    cfg = arch.moe
    axis = mi.model_axis
    nm = mi.ep_size
    E = cfg.n_experts
    E_loc = E // nm
    T, d = x.shape

    r = route(x, params["w_router"], cfg)
    cap = capacity(T, cfg, E)
    disp = dispatch(x, r, E, cap)

    # ⑤ dispatch: (E, cap, d) -> (E_loc, nm * cap, d)
    buf = disp.buf.reshape(nm, E_loc, cap, d)
    buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1, tiled=False)

    _check_expert_exec(cfg)
    exec_dropped = jnp.zeros((), jnp.int32)
    if cfg.expert_exec in _DUAL_MODES:
        # every (local expert, source shard) capacity segment is its own
        # ragged group; segment sizes come from the shards' routed counts
        # (one tiny all_gather — the paper's routing-map AllGather ③).
        shard = jax.lax.axis_index(axis)
        counts_all = jax.lax.all_gather(r.counts, axis)  # (nm, E)
        local = jax.lax.dynamic_slice(
            counts_all, (0, shard * E_loc), (nm, E_loc)
        )
        sizes = jnp.minimum(local.T, cap)  # (E_loc, nm)
        sieve = resolve_sieve_state(cfg, d, sieve)
        y_buf, exec_dropped = experts_ffn_dual_segmented(
            params, buf, sizes, cfg, sieve=sieve
        )
        y_buf = y_buf.reshape(E_loc, nm * cap, d)
    else:
        y_buf = experts_ffn(params, buf.reshape(E_loc, nm * cap, d))

    # ⑨ combine: reverse the exchange
    y_buf = y_buf.reshape(E_loc, nm, cap, d)
    y_buf = jax.lax.all_to_all(y_buf, axis, split_axis=1, concat_axis=0, tiled=False)
    y_buf = y_buf.reshape(E, cap, d)

    y = combine(y_buf, disp.slot_of, r.weights, T)
    counts = r.counts
    aux = r.aux_loss
    dropped = disp.n_dropped + exec_dropped
    axes = tuple(mi.data_axes) + (axis,)
    counts = jax.lax.psum(counts, axes)
    aux = jax.lax.pmean(aux, axes)
    dropped = jax.lax.psum(dropped, axes)
    return MoEOut(y, aux, counts, dropped)


def moe_block(
    params: dict,
    x: jax.Array,  # (B, S, d) activations
    arch: ArchConfig,
    mi: MeshInfo = LOCAL_MESH,
    sieve: Optional[SieveState] = None,
) -> MoEOut:
    """Full MoE block: routed experts (+EP) and shared experts.

    ``sieve`` is the engine-exported cost-model state consumed by
    ``expert_exec="dual_path_cost"`` (ignored by the other modes; the
    roofline default is used when it is needed but absent).  Shared
    experts run outside the shard_map as plain tensor-parallel dense
    MLPs (every token visits them — the paper's early-weight-load case),
    scaled by ``sigmoid(x w_expert_gate)`` where ``cfg.shared_gate``."""
    cfg = arch.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    # resolve once, outside the shard_map, so the state enters the EP
    # bodies through in_specs (replicated) rather than closure capture
    sieve = resolve_sieve_state(cfg, d, sieve)

    ep = mi.mesh is not None and mi.ep_size > 1 and cfg.n_experts % mi.ep_size == 0
    if ep and cfg.n_held != cfg.n_experts:
        raise ValueError(
            "a held expert share runs on one device, without an "
            "expert-parallel mesh"
        )
    if ep:
        dp_size = 1
        for a in mi.data_axes:
            dp_size *= mi.mesh.shape[a]
        if (B * S) % dp_size:
            # a prefill chunk of one slot may not split over the data
            # shards: each data shard then routes every token itself
            mi, dp_size = mi._replace(data_axes=()), 1
        use_a2a = (
            os.environ.get("REPRO_EP_MODE", "psum") == "a2a"
            and (B * S) % (dp_size * mi.ep_size) == 0
        )
        routed_params = {
            k: params[k] for k in ("w_router", "w_gate", "w_up", "w_down")
        }
        w_specs = {
            "w_router": P(None, None),
            "w_gate": P(mi.model_axis, None, None),
            "w_up": P(mi.model_axis, None, None),
            "w_down": P(mi.model_axis, None, None),
        }
        dp = mi.data_axes if mi.data_axes else None
        body = _ep_a2a_body if use_a2a else _ep_body
        token_spec = (
            P(tuple(mi.data_axes) + (mi.model_axis,), None)
            if use_a2a
            else P(dp, None)
        )
        out_specs = MoEOut(token_spec, P(), P(), P())
        if sieve is not None:
            routed = _shard_map(
                lambda p, t, s: body(p, t, arch, mi, sieve=s),
                mesh=mi.mesh,
                in_specs=(w_specs, token_spec, SieveState(P(), P())),
                out_specs=out_specs,
            )(routed_params, xt, sieve)
        else:
            routed = _shard_map(
                lambda p, t: body(p, t, arch, mi),
                mesh=mi.mesh,
                in_specs=(w_specs, token_spec),
                out_specs=out_specs,
            )(routed_params, xt)
    else:
        routed = moe_local(
            {k: v for k, v in params.items() if k != "shared"},
            xt, arch, sieve=sieve,
        )

    y = routed.y
    if cfg.n_shared:
        sp = params["shared"]
        gate = xt @ sp["w_gate"]
        up = xt @ sp["w_up"]
        shared = (jax.nn.silu(gate) * up) @ sp["w_down"]
        if cfg.shared_gate:
            g = jax.nn.sigmoid((xt @ sp["w_expert_gate"]).astype(jnp.float32))
            shared = (shared.astype(jnp.float32) * g).astype(shared.dtype)
        y = y + shared

    return MoEOut(y.reshape(B, S, d), routed.aux_loss, routed.counts, routed.n_dropped)


# ---------------------------------------------------------------------------
# Dense per-expert reference (tests only — O(T * E) memory)
# ---------------------------------------------------------------------------


def moe_reference(params: dict, x: jax.Array, arch: ArchConfig) -> jax.Array:
    """Exact routed-expert output of the held experts without capacity
    limits (oracle)."""
    cfg = arch.moe
    T, d = x.shape
    r = route(x, params["w_router"], cfg)
    y = jnp.zeros((T, d), jnp.float32)
    for j in range(cfg.n_held):
        e = cfg.held_offset + j
        gate = x @ params["w_gate"][j]
        up = x @ params["w_up"][j]
        ye = (jax.nn.silu(gate) * up) @ params["w_down"][j]
        w_e = jnp.sum(
            jnp.where(r.expert_idx == e, r.weights, 0.0).astype(jnp.float32), axis=1
        )
        y = y + ye.astype(jnp.float32) * w_e[:, None]
    return y.astype(x.dtype)
