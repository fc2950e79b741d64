"""Attention: GQA (flash-style prefill, cached decode), MLA, cross-attn.

Prefill uses a chunked online-softmax formulation (jnp + lax.scan) so the
32k/500k shapes never materialize full score matrices; the Pallas kernels
in :mod:`repro.kernels` provide the TPU-optimized versions of the same math
(decode attention), validated against these references.
"""

from __future__ import annotations

import math
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig
from .layers import _he, apply_mrope, apply_rope, softmax_mscale

NEG_INF = -1e30


def _flash_decode_mode() -> str:
    """Decode-attention backend, dual-path convention (cf. expert_exec):
    ``"kernel"`` — Pallas flash-decode on TPU; ``"xla"`` — the XLA twin on
    CPU hosts (interpret-mode Pallas is too slow to serve from);
    ``"oracle"`` — the dense reference einsum, forced by
    ``REPRO_FLASH_DECODE=0``."""
    if os.environ.get("REPRO_FLASH_DECODE", "1") in ("0", "false", "False"):
        return "oracle"
    return "kernel" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: AttnConfig, d_model: int, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 4)
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p = {
        "wq": _he(ks[0], (d_model, H * dh), 1.0, dtype),
        "wk": _he(ks[1], (d_model, K * dh), 1.0, dtype),
        "wv": _he(ks[2], (d_model, K * dh), 1.0, dtype),
        "wo": _he(ks[3], (H * dh, d_model), 1.0, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((K * dh,), dtype)
        p["bv"] = jnp.zeros((K * dh,), dtype)
    return p


def init_mla(key, cfg: AttnConfig, d_model: int, dtype=jnp.bfloat16) -> dict:
    m = cfg.mla
    H = cfg.n_heads
    ks = jax.random.split(key, 7)
    return {
        "w_dq": _he(ks[0], (d_model, m.q_lora_rank), 1.0, dtype),
        "q_norm_scale": jnp.ones((m.q_lora_rank,), jnp.float32),
        "w_uq": _he(
            ks[1], (m.q_lora_rank, H * (m.qk_nope_dim + m.qk_rope_dim)), 1.0, dtype
        ),
        "w_dkv": _he(ks[2], (d_model, m.kv_lora_rank), 1.0, dtype),
        "kv_norm_scale": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "w_kr": _he(ks[3], (d_model, m.qk_rope_dim), 1.0, dtype),
        "w_uk": _he(ks[4], (m.kv_lora_rank, H * m.qk_nope_dim), 1.0, dtype),
        "w_uv": _he(ks[5], (m.kv_lora_rank, H * m.v_head_dim), 1.0, dtype),
        "wo": _he(ks[6], (H * m.v_head_dim, d_model), 1.0, dtype),
    }


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash-style chunked attention (prefill / training)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dh)
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax blockwise attention; supports GQA via head groups.

    Memory is O(q_chunk * kv_chunk) per (batch, head) instead of O(Sq*Sk).
    ``q_offset`` places the query block inside the kv timeline (for chunked
    prefill where queries start mid-sequence).  ``scale`` is the softmax
    scale (default ``1/sqrt(dh)``).
    """
    B, Sq, H, dh = q.shape
    _, Sk, K, _ = k.shape
    dv = v.shape[-1]  # value head dim may differ (MLA)
    G = H // K
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, q_chunk, Sk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    if scale is None:
        scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    # Head-major layout: repeat kv heads to the full query head count so
    # tensor parallelism shards the head dim cleanly (GQA-aware grouping
    # lives in the Pallas kernels; here clean sharding wins).
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qg = q.reshape(B, nq, q_chunk, H, dh).astype(jnp.float32)
    kg = k.reshape(B, nk, kv_chunk, H, dh).astype(jnp.float32)
    vg = v.reshape(B, nk, kv_chunk, H, dv).astype(jnp.float32)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Sk).reshape(nk, kv_chunk)

    def per_q_chunk(qi, q_blk):
        # q_blk: (B, q_chunk, H, dh)
        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk, v_blk = kg[:, ki], vg[:, ki]  # (B, kv_chunk, H, dh)
            s = jnp.einsum("bqhd,bthd->bhqt", q_blk, k_blk) * scale
            if causal:
                mask = q_pos[qi][:, None] >= k_pos[ki][None, :]  # (qc, tc)
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqt,bthd->bhqd", p, v_blk
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, H, q_chunk, dv)

    outs = jax.lax.map(lambda qi: per_q_chunk(qi, qg[:, qi]), jnp.arange(nq))
    # (nq, B, H, q_chunk, dv) -> (B, nq, q_chunk, H, dv) -> (B, Sq, H, dv)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, dv)
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # (B, 1, H, dh)
    cache_k: jax.Array,  # (B, K, T, dh) head-major
    cache_v: jax.Array,  # (B, K, T, dh)
    length: jax.Array,  # (B,) valid cache entries (incl. current token)
) -> jax.Array:
    """One-token GQA attention against the KV cache (the memory-bound GEMV
    op the paper offloads to PIM; Pallas version in kernels/decode_attention)."""
    B, _, H, dh = q.shape
    K, T = cache_k.shape[1], cache_k.shape[2]
    G = H // K
    qf = q.reshape(B, K, G, dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qf, cache_k.astype(jnp.float32))
    s = s / jnp.sqrt(dh)
    mask = jnp.arange(T)[None, :] < length[:, None]  # (B, T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, cache_v.astype(jnp.float32))
    return o.reshape(B, 1, H, dh).astype(q.dtype)


def _per_head_shard(kernel, mi, cfg: AttnConfig, batch_sharded: bool):
    """``kernel(q (B, H, dh), k, v (B|pool, K, T|page, dh), *rest)`` as the
    step calls it.  XLA cannot partition a Pallas kernel, so on a mesh it
    runs under ``shard_map``: each model shard attends its own kv heads
    and their query-head groups (q's heads are kv-group-major), and the
    batch stays split over the data axes where the cache is per slot (a
    paged pool is shared by all slots, so it is not)."""
    if mi is None or mi.mesh is None:
        return kernel
    from jax.sharding import PartitionSpec as P

    dp = (mi.data_axes or None) if batch_sharded else None
    m = mi.model_axis if cfg.n_kv_heads % mi.ep_size == 0 else None
    q_spec = P(dp, m, None)
    kv_spec = P(dp, m, None, None)
    if batch_sharded:  # (q, k, v, lengths)
        rest = (P(dp),)
    else:  # (q, pool_k, pool_v, block_tables, lengths)
        rest = (P(None, None), P(None))
    return jax.shard_map(
        kernel, mesh=mi.mesh, in_specs=(q_spec, kv_spec, kv_spec) + rest,
        out_specs=q_spec, check_vma=False,
    )


# ---------------------------------------------------------------------------
# GQA wrappers
# ---------------------------------------------------------------------------


def _rope_or_mrope(x, positions, cfg: AttnConfig, mrope_positions):
    if cfg.mrope_sections is not None and mrope_positions is not None:
        return apply_mrope(x, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    if positions is None:
        return x
    return apply_rope(x, positions, cfg.rope_theta)


def gqa_project_qkv(
    params: dict,
    x: jax.Array,  # (B, S, d)
    positions: Optional[jax.Array],
    cfg: AttnConfig,
    mrope_positions: Optional[jax.Array] = None,
    use_rope: bool = True,
):
    B, S, _ = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, K, dh)
    v = v.reshape(B, S, K, dh)
    if use_rope:
        q = _rope_or_mrope(q, positions, cfg, mrope_positions)
        k = _rope_or_mrope(k, positions, cfg, mrope_positions)
    return q, k, v


@jax.named_scope("attention")
def gqa_prefill(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: AttnConfig,
    mrope_positions=None,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns ``(y, k, v)`` with k/v in the head-major cache layout
    ``(B, K, S, dh)`` (see :meth:`repro.models.model.LM.init_cache`)."""
    use_rope = cfg.mrope_sections is not None or positions is not None
    q, k, v = gqa_project_qkv(params, x, positions, cfg, mrope_positions, use_rope)
    o = flash_attention(q, k, v, causal=causal, q_chunk=q_chunk, kv_chunk=kv_chunk)
    B, S = x.shape[:2]
    y = o.reshape(B, S, -1) @ params["wo"]
    return y, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


@jax.named_scope("attention")
def gqa_decode(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    position: jax.Array,  # (B,) current position
    cache_k: jax.Array,
    cache_v: jax.Array,
    cfg: AttnConfig,
    mrope_positions=None,
    use_rope: bool = True,
    mi=None,  # MeshInfo: on a mesh the kernel runs per kv-head shard
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step over the head-major ``(B, K, T, dh)`` cache;
    returns the output and the updated cache."""
    pos = position[:, None] if position is not None else None
    q, k1, v1 = gqa_project_qkv(params, x, pos, cfg, mrope_positions, use_rope)
    B = x.shape[0]
    # insert current kv at `position`: the (K, 1, dh) row of each slot
    idx = position if position is not None else jnp.zeros((B,), jnp.int32)

    def insert(c, r, i):
        return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), (0, i, 0))

    cache_k = jax.vmap(insert)(cache_k, k1.transpose(0, 2, 1, 3), idx)
    cache_v = jax.vmap(insert)(cache_v, v1.transpose(0, 2, 1, 3), idx)
    if _flash_decode_mode() == "kernel":
        from repro.kernels import ops as kernel_ops

        o = _per_head_shard(
            kernel_ops.decode_attention, mi, cfg, batch_sharded=True
        )(q[:, 0], cache_k, cache_v, idx + 1)
        o = o[:, None]
    else:
        # the dense einsum is both the XLA twin and the oracle here
        o = decode_attention_ref(q, cache_k, cache_v, idx + 1)
    y = o.reshape(B, 1, -1) @ params["wo"]
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# Paged decode (shared block pool + per-slot block tables)
# ---------------------------------------------------------------------------


def paged_decode_attention_ref(
    q: jax.Array,  # (B, 1, H, dh)
    pool_k: jax.Array,  # (n_pool, Kv, page, dh) head-major
    pool_v: jax.Array,  # (n_pool, Kv, page, dh)
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,  # (B,)
) -> jax.Array:
    """Oracle: gather each slot's blocks into a dense cache, then run the
    dense reference."""
    from repro.kernels.ref import gather_pages

    return decode_attention_ref(
        q, gather_pages(pool_k, block_tables), gather_pages(pool_v, block_tables),
        lengths,
    )


def paged_decode_attention_xla(
    q: jax.Array,  # (B, 1, H, dh)
    pool_k: jax.Array,  # (n_pool, Kv, page, dh) head-major
    pool_v: jax.Array,  # (n_pool, Kv, page, dh)
    owner: jax.Array,  # (n_pool,) int32 slot owning each block, -1 free
    block_pos: jax.Array,  # (n_pool,) int32 logical index within owner
    lengths: jax.Array,  # (B,)
) -> jax.Array:
    """Pool-major XLA twin of the paged flash-decode kernel.

    Iterates physical blocks instead of (slot, max_seq) positions: each
    pool block computes its partial (m, l, acc) against its owner's query
    and a segment-reduce combines per slot — compute and memory traffic
    scale with ``n_pool * page`` (the tokens actually resident) rather
    than ``n_slots * max_seq``, which is the whole padding win on
    non-TPU hosts.
    """
    B, _, H, dh = q.shape
    n_pool, Kv, page, _ = pool_v.shape
    G = H // Kv
    qf = q.reshape(B, Kv, G, dh).astype(jnp.float32)
    own = jnp.clip(owner, 0, B - 1)
    qp = qf[own]  # (n_pool, Kv, G, dh) — free blocks get slot 0's q, masked
    s = jnp.einsum(
        "pkgd,pktd->pkgt", qp, pool_k.astype(jnp.float32)
    ) / jnp.sqrt(dh).astype(jnp.float32)
    pos = block_pos[:, None] * page + jnp.arange(page)[None, :]  # (n_pool, page)
    valid = (owner[:, None] >= 0) & (pos < lengths[own][:, None])
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    # two-pass softmax across each owner's blocks via segment reductions;
    # free blocks land in the B-th (discarded) segment
    seg = jnp.where(owner >= 0, owner, B).astype(jnp.int32)
    m_blk = s.max(axis=-1)  # (n_pool, Kv, G)
    m_slot = jax.ops.segment_max(m_blk, seg, num_segments=B + 1)[:B]
    m_slot = jnp.maximum(m_slot, NEG_INF)  # slots with no blocks: -inf -> finite
    m_of_blk = jnp.concatenate(
        [m_slot, jnp.zeros((1,) + m_slot.shape[1:], m_slot.dtype)], axis=0
    )[seg]
    p = jnp.where(valid[:, None, None], jnp.exp(s - m_of_blk[..., None]), 0.0)
    l_blk = p.sum(axis=-1)  # (n_pool, Kv, G)
    acc_blk = jnp.einsum("pkgt,pktd->pkgd", p, pool_v.astype(jnp.float32))
    l_slot = jax.ops.segment_sum(l_blk, seg, num_segments=B + 1)[:B]
    acc = jax.ops.segment_sum(acc_blk, seg, num_segments=B + 1)[:B]
    out = acc / jnp.maximum(l_slot, 1e-30)[..., None]
    return out.reshape(B, 1, H, dh).astype(q.dtype)


@jax.named_scope("attention")
def gqa_decode_paged(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    position: jax.Array,  # (B,) current position
    pool_k: jax.Array,  # (n_pool, Kv, page, dh) head-major
    pool_v: jax.Array,  # (n_pool, Kv, page, dh)
    paged: Tuple[jax.Array, jax.Array, jax.Array],  # (block_tables, owner, block_pos)
    cfg: AttnConfig,
    mrope_positions=None,
    use_rope: bool = True,
    mi=None,  # MeshInfo: on a mesh the kernel runs per kv-head shard
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One paged decode step: scatter the new KV row into the shared block
    pool through the slot's block table, then attend over the slot's
    logical blocks only.  Idle slots resolve to the trash block (physical
    0, owner -1) so their masked write never corrupts live data."""
    block_tables, owner, block_pos = paged
    pos = position[:, None]
    q, k1, v1 = gqa_project_qkv(params, x, pos, cfg, mrope_positions, use_rope)
    B = x.shape[0]
    page = pool_k.shape[2]
    phys = jnp.take_along_axis(
        block_tables, (position // page)[:, None], axis=1
    )[:, 0]
    off = position % page
    # advanced indices split by the head slice: the update is (B, Kv, dh)
    pool_k = pool_k.at[phys, :, off].set(k1[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[phys, :, off].set(v1[:, 0].astype(pool_v.dtype))
    lengths = position + 1
    mode = _flash_decode_mode()
    if mode == "kernel":
        from repro.kernels import ops as kernel_ops

        o = _per_head_shard(
            kernel_ops.decode_attention_paged, mi, cfg, batch_sharded=False
        )(q[:, 0], pool_k, pool_v, block_tables, lengths)
        o = o[:, None]
    elif mode == "xla":
        o = paged_decode_attention_xla(
            q, pool_k, pool_v, owner, block_pos, lengths
        )
    else:
        o = paged_decode_attention_ref(
            q, pool_k, pool_v, block_tables, lengths
        )
    y = o.reshape(B, 1, -1) @ params["wo"]
    return y, pool_k, pool_v


def quantize_kv_row(row: jax.Array):
    """Per-(token, head) int8 quantization: row (B, K, 1, dh) -> (q, scale)."""
    m = jnp.max(jnp.abs(row.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(m, 1e-8) / 127.0
    q = jnp.clip(jnp.round(row.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return q, scale[..., 0]  # (B, K, 1, dh) int8, (B, K, 1) f32


@jax.named_scope("attention")
def gqa_decode_seqpar(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    position: jax.Array,  # (B,)
    cache_k: jax.Array,  # (B, K, T, dh) — T sharded over the model axis
    cache_v: jax.Array,
    cfg: AttnConfig,
    mi,  # MeshInfo
    use_rope: bool = True,
    kv_scales=None,  # (k_scale, v_scale) (B, K, T) f32 — int8 KV mode
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel decode attention (§Perf iteration A).

    When GQA kv-head counts don't divide the TP degree, the KV cache is
    sharded along the *sequence* dim.  Under plain GSPMD the per-step
    dynamic cache insert forces an involuntary full rematerialization of
    the layer's cache on every device (~2 x B_loc x T x K x dh bytes/layer).
    This path instead runs the update + attention inside shard_map: each
    model shard inserts the new KV row only if it owns the slot, computes a
    partial online-softmax (m, l, acc) over its T/TP slice, and the partials
    merge with two tiny psums — per-device HBM traffic drops by the TP
    degree and no reshard/gather is emitted.
    """
    from jax.sharding import PartitionSpec as P

    pos1 = position[:, None]
    q, k1, v1 = gqa_project_qkv(params, x, pos1 if use_rope else None, cfg,
                                None, use_rope)
    k1, v1 = k1.transpose(0, 2, 1, 3), v1.transpose(0, 2, 1, 3)  # (B, K, 1, dh)
    B = x.shape[0]
    axis = mi.model_axis
    dp = mi.data_axes if mi.data_axes else None
    int8_kv = kv_scales is not None
    if int8_kv:
        k1q, k1s = quantize_kv_row(k1)
        v1q, v1s = quantize_kv_row(v1)
        k1, v1 = k1q, v1q
        ksc, vsc = kv_scales
    else:
        k1s = v1s = jnp.zeros(k1.shape[:3], jnp.float32)
        ksc = vsc = jnp.zeros(cache_k.shape[:3], jnp.float32)

    def body(q_, k1_, v1_, k1s_, v1s_, ck, cv, cks, cvs, pos):
        # per-shard: ck/cv (B_loc, K, T_loc, dh); q_ (B_loc, 1, H, dh)
        T_loc = ck.shape[2]
        shard = jax.lax.axis_index(axis)
        local = pos - shard * T_loc
        own = (local >= 0) & (local < T_loc)
        idx = jnp.clip(local, 0, T_loc - 1)

        def upd(c, row, i, o):
            new = jax.lax.dynamic_update_slice(c, row, (0, i) + (0,) * (c.ndim - 2))
            return jnp.where(o, new, c)

        ck = jax.vmap(upd)(ck, k1_, idx, own)
        cv = jax.vmap(upd)(cv, v1_, idx, own)
        if int8_kv:
            cks = jax.vmap(upd)(cks, k1s_, idx, own)
            cvs = jax.vmap(upd)(cvs, v1s_, idx, own)

        # partial attention over the local slice
        K_, dh = ck.shape[1], ck.shape[3]
        H = q_.shape[2]
        G = H // K_
        qf = q_.reshape(-1, K_, G, dh).astype(jnp.float32)
        s = jnp.einsum("bkgd,bktd->bkgt", qf, ck.astype(jnp.float32))
        if int8_kv:  # fold the per-(token,head) dequant scales in
            s = s * cks[:, :, None, :]
        s = s / jnp.sqrt(dh)
        gpos = shard * T_loc + jnp.arange(T_loc)  # global positions
        mask = gpos[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m = s.max(-1)  # (B, K, G)
        p = jnp.exp(s - m[..., None])
        if int8_kv:
            pv = p * cvs[:, :, None, :]
        else:
            pv = p
        l = p.sum(-1)
        acc = jnp.einsum("bkgt,bktd->bkgd", pv, cv.astype(jnp.float32))
        # merge partials across shards (numerically exact flash merge)
        m_all = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_all)
        l_all = jax.lax.psum(l * corr, axis)
        acc_all = jax.lax.psum(acc * corr[..., None], axis)
        o = acc_all / jnp.maximum(l_all, 1e-30)[..., None]
        return o.reshape(-1, 1, H * dh).astype(x.dtype), ck, cv, cks, cvs

    o, new_k, new_v, new_ks, new_vs = _shard_map_attn(
        body, mi,
        (q, k1, v1, k1s, v1s, cache_k, cache_v, ksc, vsc, position),
        in_specs=(
            P(dp, None, None, None),
            P(dp, None, None, None),
            P(dp, None, None, None),
            P(dp, None, None),
            P(dp, None, None),
            P(dp, None, axis, None),
            P(dp, None, axis, None),
            P(dp, None, axis),
            P(dp, None, axis),
            P(dp),
        ),
        out_specs=(
            P(dp, None, None),
            P(dp, None, axis, None),
            P(dp, None, axis, None),
            P(dp, None, axis),
            P(dp, None, axis),
        ),
    )
    y = o @ params["wo"]
    if int8_kv:
        return y, (new_k, new_v, new_ks, new_vs)
    return y, (new_k, new_v)


def _shard_map_attn(body, mi, args, in_specs, out_specs):
    # the cross-shard merge is manual psums the checker cannot verify
    return jax.shard_map(
        body, mesh=mi.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(*args)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def _mla_softmax_scale(cfg: AttnConfig) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale**2 where rope is scaled."""
    m = cfg.mla
    return softmax_mscale(cfg.rope_scaling) / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)


@jax.named_scope("attention")
def mla_prefill(
    params: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,
    cfg: AttnConfig,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y, c_kv, k_rope) — the compressed caches (576 B/token/layer)."""
    m = cfg.mla
    H = cfg.n_heads
    B, S, _ = x.shape
    cq = _rms(x @ params["w_dq"], params["q_norm_scale"])
    q = (cq @ params["w_uq"]).reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    c_kv = _rms(x @ params["w_dkv"], params["kv_norm_scale"])  # (B, S, c)
    k_rope = apply_rope(
        (x @ params["w_kr"])[:, :, None, :], positions, cfg.rope_theta,
        cfg.rope_scaling,
    )[:, :, 0, :]  # (B, S, r) shared across heads
    k_nope = (c_kv @ params["w_uk"]).reshape(B, S, H, m.qk_nope_dim)
    v = (c_kv @ params["w_uv"]).reshape(B, S, H, m.v_head_dim)

    qq = jnp.concatenate([q_nope, jnp.broadcast_to(q_rope, q_rope.shape)], -1)
    kk = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_dim))],
        -1,
    )
    o = flash_attention(qq, kk, v, causal=True, q_chunk=q_chunk, kv_chunk=kv_chunk,
                        scale=_mla_softmax_scale(cfg))
    y = o.reshape(B, S, -1) @ params["wo"]
    return y, c_kv, k_rope


@jax.named_scope("attention")
def mla_decode(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    position: jax.Array,  # (B,)
    cache_ckv: jax.Array,  # (B, T, c)
    cache_kr: jax.Array,  # (B, T, r)
    cfg: AttnConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Matrix-absorbed MLA decode: attention runs in the compressed latent
    space; the cache stays (kv_lora + rope) per token."""
    m = cfg.mla
    H = cfg.n_heads
    B = x.shape[0]
    cq = _rms(x @ params["w_dq"], params["q_norm_scale"])
    q = (cq @ params["w_uq"]).reshape(B, 1, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = apply_rope(q_rope, position[:, None], cfg.rope_theta, cfg.rope_scaling)

    c1 = _rms(x @ params["w_dkv"], params["kv_norm_scale"])  # (B, 1, c)
    kr1 = apply_rope(
        (x @ params["w_kr"])[:, :, None, :], position[:, None], cfg.rope_theta,
        cfg.rope_scaling,
    )[:, :, 0, :]
    cache_ckv = jax.vmap(lambda c, r, i: jax.lax.dynamic_update_slice(c, r, (i, 0)))(
        cache_ckv, c1, position
    )
    cache_kr = jax.vmap(lambda c, r, i: jax.lax.dynamic_update_slice(c, r, (i, 0)))(
        cache_kr, kr1, position
    )

    # absorb W_uk into the query:  q_lat[b,h,c] = sum_n q_nope[b,h,n] W_uk[c,(h,n)]
    w_uk = params["w_uk"].reshape(-1, H, m.qk_nope_dim)  # (c, H, n)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = _mla_softmax_scale(cfg)
    s = (
        jnp.einsum("bhc,btc->bht", q_lat, cache_ckv.astype(jnp.float32))
        + jnp.einsum(
            "bhr,btr->bht",
            q_rope[:, 0].astype(jnp.float32),
            cache_kr.astype(jnp.float32),
        )
    ) * scale
    T = cache_ckv.shape[1]
    mask = jnp.arange(T)[None, :] < (position[:, None] + 1)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bht,btc->bhc", p, cache_ckv.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(-1, H, m.v_head_dim)  # (c, H, v)
    o = jnp.einsum("bhc,chv->bhv", ctx_lat, w_uv.astype(jnp.float32))
    y = o.reshape(B, 1, -1).astype(x.dtype) @ params["wo"]
    return y, cache_ckv, cache_kr


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def _divisor_chunk(n: int, target: int) -> int:
    """Largest chunk <= target that divides n (1500 -> 750, etc.)."""
    c = min(target, n)
    while n % c:
        c -= 1
    return max(c, 1)


@jax.named_scope("attention")
def cross_attention(
    params: dict,
    x: jax.Array,  # (B, Sq, d)
    enc_k: jax.Array,  # (B, Se, K, dh)  precomputed from encoder states
    enc_v: jax.Array,
    cfg: AttnConfig,
) -> jax.Array:
    B, Sq, _ = x.shape
    H, dh = cfg.n_heads, cfg.d_head
    q = (x @ params["wq"]).reshape(B, Sq, H, dh)
    o = flash_attention(
        q, enc_k, enc_v, causal=False,
        q_chunk=_divisor_chunk(Sq, 1024),
        kv_chunk=_divisor_chunk(enc_k.shape[1], 1024),
    )
    return o.reshape(B, Sq, -1) @ params["wo"]


def init_cross_attention(key, cfg: AttnConfig, d_model: int, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    H, dh = cfg.n_heads, cfg.d_head
    return {
        "wq": _he(ks[0], (d_model, H * dh), 1.0, dtype),
        "wk": _he(ks[1], (d_model, H * dh), 1.0, dtype),
        "wv": _he(ks[2], (d_model, H * dh), 1.0, dtype),
        "wo": _he(ks[3], (H * dh, d_model), 1.0, dtype),
    }


def project_cross_kv(params: dict, enc_states: jax.Array, cfg: AttnConfig):
    B, Se, _ = enc_states.shape
    k = (enc_states @ params["wk"]).reshape(B, Se, cfg.n_heads, cfg.d_head)
    v = (enc_states @ params["wv"]).reshape(B, Se, cfg.n_heads, cfg.d_head)
    return k, v
