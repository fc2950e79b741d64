"""Flash-decode GQA attention Pallas kernels (dense, split-KV, paged).

The decode-phase attention op — the memory-bound GEMV-shaped operation the
paper offloads to PIM (§2.2) — implemented TPU-native: one query token per
sequence attends over its KV cache with online softmax, streaming KV blocks
from HBM through VMEM.

Three variants share the same online-softmax tile update:

* :func:`decode_attention` — dense head-major ``(B, Kv, T, dh)`` cache.
  Grid (batch, kv_head, ceil(T/bt)); each KV block is one head's
  ``(bt, dh)`` tile, which is what the TPU's (sublane, lane) tiling needs
  (a token-major cache would cut the kv-head axis to 1 in the
  second-minor position, which Mosaic refuses).  The softmax state
  (m, l, acc) lives in VMEM scratch and persists across the sequential
  T-tiles.  A ragged tail tile
  (``T % bt != 0``) is masked by the same ``pos < lengths`` predicate that
  masks per-sequence cache lengths, and tiles entirely past a sequence's
  length skip their MXU work.

* split-KV (``n_splits > 1``): the T-tiles are partitioned into independent
  splits, each emitting a normalized partial output plus its log-sum-exp;
  a tiny jnp combine pass reweights the partials by ``exp(lse - lse_max)``
  — the ``OnlineSoftmax.online_fwd`` / ``combine`` idiom.

* :func:`decode_attention_paged` — block-table-indexed variant over a
  shared head-major block pool ``(n_pool, Kv, page, dh)``.  The K/V
  BlockSpec index maps resolve logical KV blocks through a scalar-prefetched
  ``(n_slots, max_blocks)`` block table, so a slot only streams the pool
  blocks it actually owns; dead table cells point at the reserved trash
  block 0 and are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_tile_update(s, v, m_ref, l_ref, acc_ref):
    """One online-softmax update with masked-tile guard.

    ``s`` (G, bt) already has dead columns at NEG_INF.  If the running max
    is still NEG_INF after this tile (nothing unmasked seen yet),
    ``exp(s - m_new)`` would be ``exp(0) = 1`` for every masked column and
    the output would become a uniform mean over garbage V rows — the guard
    forces the probabilities (and the correction term) to the identity
    update instead.
    """
    m_prev = m_ref[...]  # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    dead = m_new <= NEG_INF * 0.5
    p = jnp.where(dead, 0.0, jnp.exp(s - m_new))  # (G, bt)
    corr = jnp.where(dead, 1.0, jnp.exp(m_prev - m_new))  # (G, 1)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _masked_tile(q_ref, k_ref, v_ref, length, tile_start: jax.Array, bt: int,
                 scale: float, m_ref, l_ref, acc_ref):
    q = q_ref[...].astype(jnp.float32)  # (G, dh)
    k = k_ref[...].astype(jnp.float32)  # (bt, dh)
    v = v_ref[...].astype(jnp.float32)  # (bt, dh)
    pos = tile_start + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    pos_col = tile_start + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    valid = pos < length
    # rows past the sequence length are garbage — a ragged tail tile even
    # reads past the array edge (NaN under the interpreter); zero V so a
    # p=0 row can never poison the accumulator through 0 * NaN
    v = jnp.where(pos_col < length, v, 0.0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, bt)
    s = jnp.where(valid, s, NEG_INF)
    _online_tile_update(s, v, m_ref, l_ref, acc_ref)


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


# ---------------------------------------------------------------------------
# Dense cache
# ---------------------------------------------------------------------------


def _decode_attn_kernel(
    lengths_ref,  # (B,) int32 scalar prefetch
    q_ref,  # (G, dh)
    k_ref,  # (bt, dh)
    v_ref,  # (bt, dh)
    out_ref,  # (G, dh)
    m_ref,  # (G, 1) fp32 scratch
    l_ref,  # (G, 1) fp32 scratch
    acc_ref,  # (G, dh) fp32 scratch
    *,
    n_t_tiles: int,
    bt: int,
    scale: float,
):
    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    length = lengths_ref[b]

    @pl.when(t * bt < length)  # tiles past the length skip all MXU work
    def _tile():
        _masked_tile(
            q_ref, k_ref, v_ref, length, t * bt, bt, scale,
            m_ref, l_ref, acc_ref,
        )

    @pl.when(t == n_t_tiles - 1)
    def _finish():
        # length-0 rows never ran a tile: acc == 0, l == 0 -> zeros out
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[...] = out.astype(out_ref.dtype)


def _decode_attn_split_kernel(
    lengths_ref,  # (B,) int32 scalar prefetch
    q_ref,  # (G, dh)
    k_ref,  # (bt, dh)
    v_ref,  # (bt, dh)
    out_ref,  # (G, dh)  normalized partial for this split
    lse_ref,  # (G, 1)   log-sum-exp for this split
    m_ref,  # (G, 1) fp32 scratch
    l_ref,  # (G, 1) fp32 scratch
    acc_ref,  # (G, dh) fp32 scratch
    *,
    n_t_tiles: int,
    bt: int,
    scale: float,
):
    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    t = pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    length = lengths_ref[b]
    tile_start = (s_idx * n_t_tiles + t) * bt

    @pl.when(tile_start < length)
    def _tile():
        _masked_tile(
            q_ref, k_ref, v_ref, length, tile_start, bt, scale,
            m_ref, l_ref, acc_ref,
        )

    @pl.when(t == n_t_tiles - 1)
    def _finish():
        # online_fwd_epilogue: o /= l; lse = m + log(l).  Splits that saw
        # no live position export lse = NEG_INF so the combine drops them.
        l = l_ref[...]  # (G, 1)
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out_ref[...] = out.astype(out_ref.dtype)
        lse_ref[...] = jnp.where(
            l > 0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF
        )


def _combine_splits(out_p: jax.Array, lse: jax.Array) -> jax.Array:
    """LSE combine over the split axis.

    out_p (B, Kv, S, G, dh) normalized partials, lse (B, Kv, S, G, 1).
    ``o = sum_s o_s * exp(lse_s - lse_sum)`` with empty splits (lse at
    NEG_INF) contributing zero weight; a fully-empty row (length 0)
    combines to zeros.
    """
    lse = lse[..., 0]
    lse_max = lse.max(axis=2, keepdims=True)
    w = jnp.where(lse > NEG_INF * 0.5, jnp.exp(lse - lse_max), 0.0)
    den = w.sum(axis=2)  # (B, Kv, G)
    out = (out_p.astype(jnp.float32) * w[..., None]).sum(axis=2)
    return out / jnp.maximum(den, 1e-30)[..., None]


def decode_attention(
    q: jax.Array,  # (B, H, dh) one query token per sequence
    cache_k: jax.Array,  # (B, Kv, T, dh) head-major
    cache_v: jax.Array,  # (B, Kv, T, dh)
    lengths: jax.Array,  # (B,) int32 valid entries
    *,
    bt: int = 512,
    n_splits: int = 1,
    interpret: bool = False,
) -> jax.Array:
    B, H, dh = q.shape
    _, Kv, T, _ = cache_k.shape
    G = H // Kv
    bt = min(bt, T)
    n_tiles = -(-T // bt)  # ragged tail tile masked in-kernel
    qg = q.reshape(B, Kv, G, dh)
    scale = 1.0 / (dh**0.5)
    lengths = lengths.astype(jnp.int32)

    if n_splits <= 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Kv, n_tiles),
            in_specs=[
                pl.BlockSpec((None, None, G, dh), lambda b, h, t, L: (b, h, 0, 0)),
                pl.BlockSpec((None, None, bt, dh), lambda b, h, t, L: (b, h, t, 0)),
                pl.BlockSpec((None, None, bt, dh), lambda b, h, t, L: (b, h, t, 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, G, dh), lambda b, h, t, L: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, dh), jnp.float32),
            ],
        )
        kernel = functools.partial(
            _decode_attn_kernel, n_t_tiles=n_tiles, bt=bt, scale=scale
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Kv, G, dh), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(lengths, qg, cache_k, cache_v)
        return out.reshape(B, H, dh)

    n_splits = min(n_splits, n_tiles)
    n_t = -(-n_tiles // n_splits)  # tiles per split (last split ragged)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Kv, n_splits, n_t),
        in_specs=[
            pl.BlockSpec(
                (None, None, G, dh), lambda b, h, s, t, L: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, bt, dh),
                lambda b, h, s, t, L: (b, h, s * n_t + t, 0),
            ),
            pl.BlockSpec(
                (None, None, bt, dh),
                lambda b, h, s, t, L: (b, h, s * n_t + t, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, None, G, dh), lambda b, h, s, t, L: (b, h, s, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, None, G, 1), lambda b, h, s, t, L: (b, h, s, 0, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_attn_split_kernel, n_t_tiles=n_t, bt=bt, scale=scale
    )
    out_p, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Kv, n_splits, G, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Kv, n_splits, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "arbitrary", "arbitrary", "arbitrary", "arbitrary"
            ),
        ),
        interpret=interpret,
    )(lengths, qg, cache_k, cache_v)
    out = _combine_splits(out_p, lse)
    return out.reshape(B, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged cache (block-table indexed)
# ---------------------------------------------------------------------------


def _paged_decode_attn_kernel(
    lengths_ref,  # (B,) int32 scalar prefetch
    tables_ref,  # (B, max_blocks) int32 scalar prefetch (index maps only)
    q_ref,  # (G, dh)
    k_ref,  # (page, dh)  pool block resolved through the table
    v_ref,  # (page, dh)
    out_ref,  # (G, dh)
    m_ref,  # (G, 1) fp32 scratch
    l_ref,  # (G, 1) fp32 scratch
    acc_ref,  # (G, dh) fp32 scratch
    *,
    n_blocks: int,
    page: int,
    scale: float,
):
    del tables_ref  # consumed by the BlockSpec index maps
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    length = lengths_ref[b]

    # logical blocks past the slot's length point at the trash block and
    # skip all work — compute scales with the blocks a slot owns, not with
    # max_seq
    @pl.when(j * page < length)
    def _tile():
        _masked_tile(
            q_ref, k_ref, v_ref, length, j * page, page, scale,
            m_ref, l_ref, acc_ref,
        )

    @pl.when(j == n_blocks - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[...] = out.astype(out_ref.dtype)


def decode_attention_paged(
    q: jax.Array,  # (B, H, dh) one query token per sequence
    pool_k: jax.Array,  # (n_pool, Kv, page, dh) head-major block pool
    pool_v: jax.Array,  # (n_pool, Kv, page, dh)
    block_tables: jax.Array,  # (B, max_blocks) int32 logical -> physical
    lengths: jax.Array,  # (B,) int32 valid entries per sequence
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, dh = q.shape
    _, Kv, page, _ = pool_k.shape
    G = H // Kv
    n_blocks = block_tables.shape[1]
    qg = q.reshape(B, Kv, G, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Kv, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, None, G, dh), lambda b, h, j, L, BT: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, page, dh), lambda b, h, j, L, BT: (BT[b, j], h, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, page, dh), lambda b, h, j, L, BT: (BT[b, j], h, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, G, dh), lambda b, h, j, L, BT: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_attn_kernel,
        n_blocks=n_blocks,
        page=page,
        scale=1.0 / (dh**0.5),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), qg, pool_k, pool_v)
    return out.reshape(B, H, dh)
