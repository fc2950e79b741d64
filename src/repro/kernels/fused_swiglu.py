"""Single-pass fused SwiGLU Pallas kernels (grouped GEMM + tail GEMV).

The three-``pallas_call`` head path (``gate``/``up``/``down`` as separate
grouped matmuls) reads the capacity slab from HBM twice and round-trips the
``(G, C, d_expert)`` SiLU intermediate through HBM — exactly the bandwidth
the Sieve intensity argument is about.  These kernels fuse the whole SwiGLU
into one pass:

* :func:`fused_swiglu_gmm` — grouped head path.  Per m-tile the kernel
  accumulates the gate and up projections against *two* rhs refs over the
  k grid, applies ``silu(gate) * up`` in VMEM at the last k step, and feeds
  the product straight into the down projection, accumulating the output
  row block across the f grid.  The capacity slab is streamed once per
  f-tile (F/bf slab passes; exactly one when d_expert fits a single
  ``bf`` block — vs two full passes per n-tile sweep for the separate
  gate/up calls), the ``(bm, bf)`` intermediate never leaves VMEM, and
  only the final ``(bm, d_model)`` block is written to HBM.

* :func:`fused_swiglu_gemv` — streaming tail path.  Each row streams its
  expert's ``wg``/``wu``/``wd`` tiles exactly once with the activation held
  in-register (three ``pallas_call`` streams per row → one).

Both keep the grouped-GEMM scalar-prefetch contract (``sizes`` +
``rhs_of_group`` tile→group tables) and the dead-tile MXU skip: tiles with
no live rows run none of the three dots.

VMEM budget: the grouped kernel keeps a ``(bm, F)`` fp32 SiLU product, the
``(bm, bf)`` gate/up accumulators, one ``(bf, bn)`` weight tile, and a
``(bm, bn)`` fp32 output accumulator resident; ``bn`` defaults to the full
``d_model`` (one n-tile — identical schedule to the original single-pass
kernel) and is blocked down automatically by the ops wrapper only when the
old full ``(bm, d_model)`` accumulator would blow the VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_swiglu_gmm_kernel(
    # scalar prefetch
    group_of_tile_ref,  # (m_tiles,) int32: group id per m-tile
    row_in_group_ref,  # (m_tiles,) int32: tile's first row offset in its group
    group_sizes_ref,  # (G,) int32: actual rows per group
    rhs_of_group_ref,  # (G,) int32: weight row per group (consumed by the
    #                     wg/wu/wd BlockSpec index maps)
    # inputs
    lhs_ref,  # (bm, bk)
    wg_ref,  # (1, bk, bf)
    wu_ref,  # (1, bk, bf)
    wd_ref,  # (1, bf, bn)
    # outputs
    out_ref,  # (bm, bn)
    # scratch
    gate_acc_ref,  # (bm, bf) fp32
    up_acc_ref,  # (bm, bf) fp32
    h_ref,  # (bm, F) fp32 — full SiLU product, filled on the first n-tile
    out_acc_ref,  # (bm, bn) fp32
    *,
    n_k_tiles: int,
    n_f_tiles: int,
    n_n_tiles: int,
    bm: int,
    bf: int,
):
    del rhs_of_group_ref
    i = pl.program_id(0)
    n = pl.program_id(1)  # n tile (d_model output)
    j = pl.program_id(2)  # f tile (the SwiGLU hidden dim)
    k = pl.program_id(3)  # k tile (d_model contraction)

    @pl.when((n == 0) & (k == 0))
    def _init_gate_up():
        gate_acc_ref[...] = jnp.zeros_like(gate_acc_ref)
        up_acc_ref[...] = jnp.zeros_like(up_acc_ref)

    @pl.when((j == 0) & (k == 0))
    def _init_out():
        out_acc_ref[...] = jnp.zeros_like(out_acc_ref)

    g = group_of_tile_ref[i]
    base = row_in_group_ref[i]
    size = group_sizes_ref[g]
    live = base < size  # any real rows in this tile?

    # gate/up run once per (i, j, k) — on the first n-tile only; later
    # n-tiles reuse the SiLU product parked in h_ref
    @pl.when(live & (n == 0))
    def _gate_up():
        x = lhs_ref[...]
        gate_acc_ref[...] += jax.lax.dot_general(
            x, wg_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        up_acc_ref[...] += jax.lax.dot_general(
            x, wu_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & (n == 0) & (k == n_k_tiles - 1))
    def _activate():
        # silu(gate) * up in VMEM — the (bm, F) intermediate never touches
        # HBM; it feeds the down projection of every n-tile.
        h_ref[:, pl.ds(j * bf, bf)] = (
            jax.nn.silu(gate_acc_ref[...]) * up_acc_ref[...]
        )

    @pl.when(live & (k == n_k_tiles - 1))
    def _down():
        h = h_ref[:, pl.ds(j * bf, bf)].astype(lhs_ref.dtype)
        out_acc_ref[...] += jax.lax.dot_general(
            h, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when((j == n_f_tiles - 1) & (k == n_k_tiles - 1))
    def _finish():
        # mask rows beyond the group's real size
        rows = base + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = rows < size
        out_ref[...] = jnp.where(mask, out_acc_ref[...], 0.0).astype(
            out_ref.dtype
        )


def fused_swiglu_gmm(
    lhs: jax.Array,  # (M, K) group-major rows, groups bm-aligned
    wg: jax.Array,  # (E, K, F)
    wu: jax.Array,  # (E, K, F)
    wd: jax.Array,  # (E, F, N)
    group_sizes: jax.Array,  # (G,) int32 — real rows per group
    group_of_tile: jax.Array,  # (M//bm,) int32
    row_in_group: jax.Array,  # (M//bm,) int32
    rhs_of_group: jax.Array | None = None,  # (G,) int32 — weight row per group
    *,
    bm: int = 128,
    bk: int = 512,
    bf: int = 256,
    bn: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; use ops.swiglu_gmm_capacity for the user-facing
    wrapper.  Same layout/scalar-prefetch contract as
    :func:`repro.kernels.grouped_gemm.grouped_gemm`; ``rhs_of_group``
    defaults to the identity (group g uses expert g's weights).

    ``bn`` blocks the output d_model axis so the fp32 accumulator is
    ``(bm, bn)`` instead of the full ``(bm, d_model)``; the default (one
    n-tile) keeps the original schedule bit-for-bit."""
    M, K = lhs.shape
    E, _, F = wg.shape
    N = wd.shape[2]
    bm, bk, bf = min(bm, M), min(bk, K), min(bf, F)
    bn = N if bn is None else min(bn, N)
    assert M % bm == 0 and K % bk == 0 and F % bf == 0 and N % bn == 0, (
        M, K, F, N, bm, bk, bf, bn,
    )
    assert wu.shape == wg.shape and wd.shape[:2] == (E, F), (
        wg.shape, wu.shape, wd.shape,
    )
    m_tiles, n_tiles, f_tiles, k_tiles = M // bm, N // bn, F // bf, K // bk
    if rhs_of_group is None:
        rhs_of_group = jnp.arange(group_sizes.shape[0], dtype=jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(m_tiles, n_tiles, f_tiles, k_tiles),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, n, j, k, g, r, s, w: (i, k)),
            pl.BlockSpec(
                (1, bk, bf), lambda i, n, j, k, g, r, s, w: (w[g[i]], k, j)
            ),
            pl.BlockSpec(
                (1, bk, bf), lambda i, n, j, k, g, r, s, w: (w[g[i]], k, j)
            ),
            pl.BlockSpec(
                (1, bf, bn), lambda i, n, j, k, g, r, s, w: (w[g[i]], j, n)
            ),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda i, n, j, k, g, r, s, w: (i, n)
        ),
        scratch_shapes=[
            pltpu.VMEM((bm, bf), jnp.float32),
            pltpu.VMEM((bm, bf), jnp.float32),
            pltpu.VMEM((bm, F), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fused_swiglu_gmm_kernel,
        n_k_tiles=k_tiles,
        n_f_tiles=f_tiles,
        n_n_tiles=n_tiles,
        bm=bm,
        bf=bf,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "arbitrary", "arbitrary", "arbitrary", "arbitrary"
            ),
        ),
        interpret=interpret,
    )(
        group_of_tile,
        row_in_group,
        group_sizes.astype(jnp.int32),
        rhs_of_group.astype(jnp.int32),
        lhs,
        wg,
        wu,
        wd,
    )


def _fused_swiglu_gemv_kernel(
    expert_ids_ref,  # (S,) int32 scalar prefetch
    valid_ref,  # (S,) int32 scalar prefetch (1 = live row)
    tok_ref,  # (1, bk)
    wg_ref,  # (1, bk, bf)
    wu_ref,  # (1, bk, bf)
    wd_ref,  # (1, bf, N)
    out_ref,  # (1, N)
    gate_acc_ref,  # (1, bf) fp32
    up_acc_ref,  # (1, bf) fp32
    out_acc_ref,  # (1, N) fp32
    *,
    n_k_tiles: int,
    n_f_tiles: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init_gate_up():
        gate_acc_ref[...] = jnp.zeros_like(gate_acc_ref)
        up_acc_ref[...] = jnp.zeros_like(up_acc_ref)

    @pl.when((j == 0) & (k == 0))
    def _init_out():
        out_acc_ref[...] = jnp.zeros_like(out_acc_ref)

    live = valid_ref[i] > 0

    @pl.when(live)
    def _gate_up():
        # (1, bk) x (bk, bf): weight-tile streaming dominates (the PIM
        # regime); the row's activation stays in VMEM across all three
        # projections.
        t = tok_ref[...]
        gate_acc_ref[...] += jax.lax.dot_general(
            t, wg_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        up_acc_ref[...] += jax.lax.dot_general(
            t, wu_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & (k == n_k_tiles - 1))
    def _activate_down():
        h = (
            jax.nn.silu(gate_acc_ref[...]) * up_acc_ref[...]
        ).astype(tok_ref.dtype)
        out_acc_ref[...] += jax.lax.dot_general(
            h, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when((j == n_f_tiles - 1) & (k == n_k_tiles - 1))
    def _finish():
        out_ref[...] = jnp.where(live, out_acc_ref[...], 0.0).astype(
            out_ref.dtype
        )


def fused_swiglu_gemv(
    tokens: jax.Array,  # (S, K)
    wg: jax.Array,  # (E, K, F)
    wu: jax.Array,  # (E, K, F)
    wd: jax.Array,  # (E, F, N)
    expert_ids: jax.Array,  # (S,) int32
    valid: jax.Array,  # (S,) int32
    *,
    bk: int = 512,
    bf: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; use ops.swiglu_gemv for the user-facing wrapper.

    Per token i: ``out[i] = swiglu(tokens[i]; wg/wu/wd[expert_ids[i]])`` —
    each row's expert weights are streamed from HBM exactly once.

    Tokens and output are viewed as ``(S, 1, K)`` / ``(S, 1, N)`` with the
    row axis squeezed out of the block, so each block's last two dims are
    ``(1, bk)`` / ``(1, N)`` — equal to the array's own unit dim, which the
    TPU's tiling rule accepts (a ``(1, bk)`` block over ``(S, K)`` is
    refused)."""
    S, K = tokens.shape
    E, _, F = wg.shape
    N = wd.shape[2]
    bk, bf = min(bk, K), min(bf, F)
    assert K % bk == 0 and F % bf == 0, (K, F, bk, bf)
    k_tiles, f_tiles = K // bk, F // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, f_tiles, k_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, bk), lambda i, j, k, e, v: (i, 0, k)),
            pl.BlockSpec((1, bk, bf), lambda i, j, k, e, v: (e[i], k, j)),
            pl.BlockSpec((1, bk, bf), lambda i, j, k, e, v: (e[i], k, j)),
            pl.BlockSpec((1, bf, N), lambda i, j, k, e, v: (e[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, N), lambda i, j, k, e, v: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, bf), jnp.float32),
            pltpu.VMEM((1, bf), jnp.float32),
            pltpu.VMEM((1, N), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fused_swiglu_gemv_kernel, n_k_tiles=k_tiles, n_f_tiles=f_tiles
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, N), tokens.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(expert_ids, valid, tokens.reshape(S, 1, K), wg, wu, wd)
    return out.reshape(S, N)
