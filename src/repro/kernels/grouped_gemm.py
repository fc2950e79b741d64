"""Ragged grouped matmul Pallas kernel (megablox-lite).

The GPU-side path of the Sieve split: popular experts execute as one
grouped GEMM over expert-major token buffers (paper §6.3 "grouped GEMM or
batch matrix multiplication").  On TPU this is an MXU kernel whose m-tiles
map to (expert, row-block) pairs through a scalar-prefetched tile→group
table, so per-expert row counts can vary at runtime without recompilation.

Layout contract (enforced by ops.py): tokens are expert-major and each
group's rows are padded to a multiple of ``bm`` (our capacity-based MoE
dispatch produces exactly this layout), so no m-tile spans two groups.

Groups are decoupled from weight rows via a scalar-prefetched
``rhs_of_group`` table: several groups may share one expert's weights —
the expert-parallel a2a layout needs this, where each local expert's rows
arrive as one segment per source shard and every (expert, shard) segment
is its own ragged group.

Tiles: lhs (bm, bk) / rhs (1, bk, bn) / out (bm, bn), fp32 accumulation in
VMEM scratch.  Tiles whose rows are entirely padding skip the MXU work
(``pl.when`` on the prefetched group sizes) — this is the measurable win of
the Sieve dual path over naive capacity-dense batched matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(
    # scalar prefetch
    group_of_tile_ref,  # (m_tiles,) int32: group id per m-tile
    row_in_group_ref,  # (m_tiles,) int32: tile's first row offset in its group
    group_sizes_ref,  # (G,) int32: actual rows per group
    rhs_of_group_ref,  # (G,) int32: weight row per group (unused in body;
    #                     consumed by the rhs BlockSpec index map)
    # inputs
    lhs_ref,  # (bm, bk)
    rhs_ref,  # (1, bk, bn)
    # outputs
    out_ref,  # (bm, bn)
    # scratch
    acc_ref,  # (bm, bn) fp32
    *,
    n_k_tiles: int,
    bm: int,
):
    del rhs_of_group_ref
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = group_of_tile_ref[i]
    base = row_in_group_ref[i]
    size = group_sizes_ref[g]
    live = base < size  # any real rows in this tile?

    @pl.when(live)
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...],
            rhs_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == n_k_tiles - 1)
    def _finish():
        # mask rows beyond the group's real size
        rows = base + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = rows < size
        out_ref[...] = jnp.where(mask, acc_ref[...], 0.0).astype(out_ref.dtype)


def grouped_gemm(
    lhs: jax.Array,  # (M, K) group-major rows, groups bm-aligned
    rhs: jax.Array,  # (E, K, N)
    group_sizes: jax.Array,  # (G,) int32 — real rows per group
    group_of_tile: jax.Array,  # (M//bm,) int32
    row_in_group: jax.Array,  # (M//bm,) int32
    rhs_of_group: jax.Array | None = None,  # (G,) int32 — weight row per group
    *,
    bm: int = 128,
    bk: int = 512,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; use ops.gmm_capacity / ops.gmm_ragged for the
    user-facing wrappers.  ``rhs_of_group`` defaults to the identity
    (group g multiplies rhs[g])."""
    M, K = lhs.shape
    E, _, N = rhs.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    m_tiles, n_tiles, k_tiles = M // bm, N // bn, K // bk
    if rhs_of_group is None:
        rhs_of_group = jnp.arange(group_sizes.shape[0], dtype=jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(m_tiles, n_tiles, k_tiles),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, g, r, s, w: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, g, r, s, w: (w[g[i]], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, g, r, s, w: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_gmm_kernel, n_k_tiles=k_tiles, bm=bm)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        group_of_tile,
        row_in_group,
        group_sizes.astype(jnp.int32),
        rhs_of_group.astype(jnp.int32),
        lhs,
        rhs,
    )
