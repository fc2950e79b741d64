"""jit'd public wrappers for the Pallas kernels.

Handles metadata construction (tile→group tables), block-size selection,
padding to tile multiples, and interpret-mode selection (CPU containers run
the kernels in interpret=True; on TPU they compile to Mosaic).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention as _decode_attention
from .decode_attention import decode_attention_paged as _decode_attention_paged
from .fused_swiglu import fused_swiglu_gemv as _fused_swiglu_gemv
from .fused_swiglu import fused_swiglu_gmm as _fused_swiglu_gmm


def _interpret_default() -> bool:
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# Mosaic's second-minor ("sublane") tiling granularity: m-block sizes must
# be multiples of this or the TPU lowering mis-tiles (fp32 tile = (8, 128);
# bf16's (16, 128) packs two fp32 sublanes, so 8 remains the common base).
_SUBLANE = 8


def _clamp_bm(bm: int, rows: int) -> int:
    """Clamp the m-block size to the row count without leaving the sublane
    grid: ``min(bm, rows)`` alone can yield a non-tile-aligned ``bm`` for
    small row counts (e.g. rows=12 -> bm=12), which Mosaic rejects.  Rounds
    the clamp target up to a sublane multiple (the wrapper pads rows), then
    rounds the result down so it stays a valid tile height."""
    bm = min(bm, _round_up(max(rows, 1), _SUBLANE))
    bm = max(_SUBLANE, (bm // _SUBLANE) * _SUBLANE)
    assert bm % _SUBLANE == 0 and bm >= _SUBLANE, bm
    return bm


def _fit_block(b: int, dim: int) -> int:
    """Largest block size <= ``b`` that divides ``dim`` (k/n tile dims are
    not padded by the wrappers, so the block must divide exactly).  For
    power-of-two defaults this is gcd, which keeps the big power-of-two
    factor — e.g. dim=768 (qwen3 d_expert) with the default b=512 -> 256,
    where ``min`` alone would leave a block that does not divide it."""
    b = min(b, dim)
    if dim % b:
        b = math.gcd(b, dim)
    return b


# ---------------------------------------------------------------------------
# Fused SwiGLU grouped GEMM (single-pass head path)
# ---------------------------------------------------------------------------


def _capacity_tiles(buf: jax.Array, bm: int):
    """Capacity-layout prologue of the head kernel: clamp the m-block to
    the (padded) capacity, pad C to a multiple of it, flatten to
    group-major rows, and build the tile→group scalar-prefetch tables.
    Returns ``(lhs, group_of_tile, row_in_group, bm, Cp)``; each m-tile
    then belongs to exactly one group."""
    G, C, K = buf.shape
    bm = _clamp_bm(bm, C)
    Cp = _round_up(C, bm)
    if Cp != C:
        buf = jnp.pad(buf, ((0, 0), (0, Cp - C), (0, 0)))
    lhs = buf.reshape(G * Cp, K)
    tiles_per_group = Cp // bm
    m_tiles = G * tiles_per_group
    group_of_tile = (
        jnp.arange(m_tiles, dtype=jnp.int32) // tiles_per_group
    )
    row_in_group = (
        jnp.arange(m_tiles, dtype=jnp.int32) % tiles_per_group
    ) * bm
    return lhs, group_of_tile, row_in_group, bm, Cp


# Soft cap for the fused-SwiGLU fp32 output accumulator: when the full
# (bm, d_model) block would exceed this many bytes, the n axis is blocked
# so large-d_model configs still fit VMEM.  qwen3-30b (bm=128, N=2048,
# 1 MB) stays a single n-tile — identical schedule to the unblocked kernel.
_SWIGLU_ACC_BUDGET = 4 * 1024 * 1024


def _fit_acc_bn(bm: int, n: int, budget: int = _SWIGLU_ACC_BUDGET) -> int:
    bn = n
    while bn > 128 and bm * bn * 4 > budget:
        bn //= 2
    return _fit_block(bn, n)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bf", "bn", "interpret"))
def swiglu_gmm_capacity(
    buf: jax.Array,  # (G, C, K) capacity-layout dispatch buffer
    wg: jax.Array,  # (E, K, F)
    wu: jax.Array,  # (E, K, F)
    wd: jax.Array,  # (E, F, N)
    group_sizes: jax.Array,  # (G,) real rows per group
    rhs_of_group: jax.Array | None = None,  # (G,) weight row per group
    bm: int = 128,
    bk: int = 512,
    bf: int = 256,
    bn: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-pass SwiGLU over the (G, C, K) capacity buffer -> (G, C, N).

    Gate, up and down projections in one kernel: the slab is streamed
    from HBM once per f-tile (F/bf passes — exactly once when the expert
    dim fits one ``bf`` block) and the ``silu(gate) * up`` intermediate
    lives only in VMEM.  C is padded to a multiple of bm so each m-tile
    belongs to one group; group g uses expert g's weights unless
    ``rhs_of_group`` maps several groups to one expert (the EP a2a
    layout, one ragged group per (expert, source shard)).  Only m-tiles
    with live rows are visited, so a dead group streams no weights.
    """
    if interpret is None:
        interpret = _interpret_default()
    G, C, K = buf.shape
    N = wd.shape[2]
    bk, bf = _fit_block(bk, K), _fit_block(bf, wg.shape[2])
    lhs, group_of_tile, row_in_group, bm, Cp = _capacity_tiles(buf, bm)
    if bn is None:
        bn = _fit_acc_bn(bm, N)
    out = _fused_swiglu_gmm(
        lhs, wg, wu, wd, group_sizes.astype(jnp.int32), group_of_tile,
        row_in_group, rhs_of_group,
        bm=bm, bk=bk, bf=bf, bn=bn, interpret=interpret,
    )
    return out.reshape(G, Cp, N)[:, :C, :]


@functools.partial(jax.jit, static_argnames=("bk", "bf", "interpret"))
def swiglu_gemv(
    tokens: jax.Array,  # (S, K)
    wg: jax.Array,  # (E, K, F)
    wu: jax.Array,  # (E, K, F)
    wd: jax.Array,  # (E, F, N)
    expert_ids: jax.Array,  # (S,) int32
    valid: jax.Array | None = None,  # (S,) bool/int
    bk: int = 512,
    bf: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused tail path: per-row SwiGLU with the row's expert's three
    weight matrices streamed once each; an invalid row is not visited and
    streams nothing."""
    if interpret is None:
        interpret = _interpret_default()
    S = tokens.shape[0]
    bk = _fit_block(bk, tokens.shape[1])
    bf = _fit_block(bf, wg.shape[2])
    if valid is None:
        valid = jnp.ones((S,), jnp.int32)
    return _fused_swiglu_gemv(
        tokens, wg, wu, wd, expert_ids.astype(jnp.int32),
        valid.astype(jnp.int32),
        bk=bk, bf=bf, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bt", "n_splits", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, H, dh)
    cache_k: jax.Array,  # (B, Kv, T, dh) head-major
    cache_v: jax.Array,
    lengths: jax.Array,  # (B,)
    bt: int = 512,
    n_splits: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash-decode over a dense per-slot cache.

    Ragged ``T % bt`` tails are masked in-kernel (no padding copy of the
    cache); ``n_splits > 1`` partitions the KV axis into independent
    splits combined by log-sum-exp.
    """
    if interpret is None:
        interpret = _interpret_default()
    return _decode_attention(
        q, cache_k, cache_v, lengths.astype(jnp.int32),
        bt=bt, n_splits=n_splits, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged(
    q: jax.Array,  # (B, H, dh)
    pool_k: jax.Array,  # (n_pool, Kv, page, dh) head-major block pool
    pool_v: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,  # (B,)
    interpret: bool | None = None,
) -> jax.Array:
    """Flash-decode over the paged block pool: each slot streams only the
    pool blocks its block-table row owns (dead cells hit the trash block
    and skip their work)."""
    if interpret is None:
        interpret = _interpret_default()
    return _decode_attention_paged(
        q, pool_k, pool_v, block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32), interpret=interpret,
    )
