"""Single-pass fused SwiGLU Pallas kernels (grouped GEMM + tail GEMV).

The expert FFN is ``down(silu(gate(x)) * up(x))``.  Run as three separate
matmuls it would read the capacity slab from HBM twice and round-trip the
``(G, C, d_expert)`` SiLU intermediate through HBM — exactly the bandwidth
the Sieve intensity argument is about.  These kernels fuse the whole SwiGLU
into one pass:

* :func:`fused_swiglu_gmm` — grouped head path.  Per m-tile the kernel
  accumulates the gate and up projections against *two* rhs refs over the
  k grid, applies ``silu(gate) * up`` in VMEM at the last k step, and feeds
  the product straight into the down projection, accumulating the output
  row block across the f grid.  The capacity slab is streamed once per
  f-tile (F/bf slab passes; exactly one when d_expert fits a single
  ``bf`` block), the ``(bm, bf)`` intermediate never leaves VMEM, and
  only the final ``(bm, d_model)`` block is written to HBM.

* :func:`fused_swiglu_gemv` — streaming tail path.  Each row streams its
  expert's ``wg``/``wu``/``wd`` tiles exactly once with the activation held
  in-register.

Only live work is visited.  The grid's first axis runs over the live
m-tiles (head) or valid rows (tail) alone: :func:`gmm_fetch_tables` /
:func:`gemv_fetch_tables` order them first and the grid is bounded at
their scalar-prefetched count, so a tile or row with nothing to compute
costs no grid step and no weight, token or output copy.  Its output block
is never written and keeps the zeros of the buffer aliased to the output;
a call with no live work at all skips the kernel.

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


def _live_first(live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(count, order): the indices of the live entries first, in their
    own order, then the dead ones."""
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(jnp.int32)
    return live.sum(dtype=jnp.int32), order


def _skip_if_idle(name, run, n_live, *operands, out_shape, dtype):
    """``run(n_live, *operands, zeros)``, with the zero buffer its kernel
    writes into, where anything is live; the zeros alone where nothing is
    (a grid of no steps is not issued).  ``run`` is traced as a function
    of its own named ``name``: the compiled program names the kernel after
    the function it sits in (else the branch's), and the benchmark finds
    the expert kernels in a device trace by the names of their ``ops``
    wrappers."""
    run.__name__ = run.__qualname__ = name
    zeros = jnp.zeros(out_shape, dtype)
    return jax.lax.cond(
        n_live > 0, jax.jit(run), lambda *a: a[-1], n_live, *operands, zeros
    )


# ---------------------------------------------------------------------------
# Grouped head path
# ---------------------------------------------------------------------------


def gmm_fetch_tables(
    group_sizes: jax.Array,  # (G,) int32: actual rows per group
    group_of_tile: jax.Array,  # (m_tiles,) int32
    row_in_group: jax.Array,  # (m_tiles,) int32: tile's first row in its group
    rhs_of_group: jax.Array,  # (G,) int32: weight row per group
    bm: int,
):
    """Scalar-prefetch tables of :func:`fused_swiglu_gmm`, one entry per
    grid step: the m-tiles with live rows first.  Returns ``(n_live,
    tile, w_row, n_rows)``: the grid's bound, then per step the m-tile it
    computes, the weight row it streams and the tile's live rows.  Steps
    past ``n_live`` are never run."""
    n_rows = jnp.clip(group_sizes[group_of_tile] - row_in_group, 0, bm)
    n_live, tile = _live_first(n_rows > 0)
    w_row = rhs_of_group[group_of_tile][tile]
    return n_live, tile, w_row.astype(jnp.int32), n_rows[tile].astype(jnp.int32)


def gmm_index_maps(f_tiles: int, k_tiles: int):
    """Block index maps of :func:`fused_swiglu_gmm` over the grid ``(i,
    n, j, k)`` and its tables ``(tile, w_row, n_rows)``: ``lhs``, ``wg``,
    ``wu``, ``wd``, ``out``.  ``wg``/``wu`` feed only the first n-tile;
    on later ones they keep naming that n-tile's last block, so the
    pipeline fetches them once per step of the first axis."""

    def lhs(i, n, j, k, t, w, r):
        return t[i], k

    def gate_up(i, n, j, k, t, w, r):
        first = n == 0
        return (
            w[i],
            jnp.where(first, k, k_tiles - 1),
            jnp.where(first, j, f_tiles - 1),
        )

    def down(i, n, j, k, t, w, r):
        return w[i], j, n

    def out(i, n, j, k, t, w, r):
        return t[i], n

    return lhs, gate_up, gate_up, down, out


def _fused_swiglu_gmm_kernel(
    # scalar prefetch
    tile_ref,  # (m_tiles,) int32: m-tile of each grid step (index maps)
    w_row_ref,  # (m_tiles,) int32: weight row of each grid step (index maps)
    n_rows_ref,  # (m_tiles,) int32: live rows of each grid step's tile
    # inputs
    lhs_ref,  # (bm, bk)
    wg_ref,  # (1, bk, bf)
    wu_ref,  # (1, bk, bf)
    wd_ref,  # (1, bf, bn)
    zeros_ref,  # (M, N) in HBM, aliased to the output
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
    bm: int,
    bf: int,
):
    del tile_ref, w_row_ref, zeros_ref
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

    # gate/up run once per (i, j, k) — on the first n-tile only; later
    # n-tiles reuse the SiLU product parked in h_ref
    @pl.when(n == 0)
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

    @pl.when((n == 0) & (k == n_k_tiles - 1))
    def _activate():
        # silu(gate) * up in VMEM — the (bm, F) intermediate never touches
        # HBM; it feeds the down projection of every n-tile.
        h_ref[:, pl.ds(j * bf, bf)] = (
            jax.nn.silu(gate_acc_ref[...]) * up_acc_ref[...]
        )

    @pl.when(k == n_k_tiles - 1)
    def _down():
        h = h_ref[:, pl.ds(j * bf, bf)].astype(lhs_ref.dtype)
        out_acc_ref[...] += jax.lax.dot_general(
            h, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when((j == n_f_tiles - 1) & (k == n_k_tiles - 1))
    def _finish():
        # mask rows beyond the group's real size
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = rows < n_rows_ref[i]
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
    wrapper.  Layout contract (built by the wrapper): rows are
    group-major and each group's rows are padded to a multiple of ``bm``,
    so no m-tile spans two groups; ``rhs_of_group`` defaults to the
    identity (group g uses expert g's weights).  Only
    m-tiles with live rows are visited (:func:`gmm_fetch_tables`); the
    rows of the others read zero.

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
    n_tiles, f_tiles, k_tiles = N // bn, F // bf, K // bk
    if rhs_of_group is None:
        rhs_of_group = jnp.arange(group_sizes.shape[0], dtype=jnp.int32)
    n_live, tile, w_row, n_rows = gmm_fetch_tables(
        group_sizes.astype(jnp.int32), group_of_tile, row_in_group,
        rhs_of_group.astype(jnp.int32), bm,
    )
    lhs_map, wg_map, wu_map, wd_map, out_map = gmm_index_maps(f_tiles, k_tiles)

    def run(n_live, tile, w_row, n_rows, lhs, wg, wu, wd, zeros):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_live, n_tiles, f_tiles, k_tiles),
            in_specs=[
                pl.BlockSpec((bm, bk), lhs_map),
                pl.BlockSpec((1, bk, bf), wg_map),
                pl.BlockSpec((1, bk, bf), wu_map),
                pl.BlockSpec((1, bf, bn), wd_map),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((bm, bn), out_map),
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
            bm=bm,
            bf=bf,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
            # operand 7: the zero buffer, after the three tables and the
            # four arrays
            input_output_aliases={7: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "arbitrary", "arbitrary", "arbitrary", "arbitrary"
                ),
            ),
            interpret=interpret,
        )(tile, w_row, n_rows, lhs, wg, wu, wd, zeros)

    return _skip_if_idle(
        "swiglu_gmm_capacity", run, n_live, tile, w_row, n_rows, lhs, wg, wu,
        wd, out_shape=(M, N), dtype=lhs.dtype,
    )


# ---------------------------------------------------------------------------
# Streaming tail path
# ---------------------------------------------------------------------------


def gemv_fetch_tables(expert_ids: jax.Array, valid: jax.Array):
    """Scalar-prefetch tables of :func:`fused_swiglu_gemv`, one entry per
    grid step: the valid rows first.  Returns ``(n_live, row, w_row)``:
    the grid's bound, then per step the row it computes and the weight
    row it streams.  Steps past ``n_live`` are never run."""
    n_live, row = _live_first(valid > 0)
    return n_live, row, expert_ids[row].astype(jnp.int32)


def gemv_index_maps():
    """Block index maps of :func:`fused_swiglu_gemv` over the grid ``(i,
    j, k)`` and its tables ``(row, w_row)``: ``tokens``, ``wg``, ``wu``,
    ``wd``, ``out``."""

    def tok(i, j, k, r, w):
        return r[i], 0, k

    def gate_up(i, j, k, r, w):
        return w[i], k, j

    def down(i, j, k, r, w):
        return w[i], j, 0

    def out(i, j, k, r, w):
        return r[i], 0, 0

    return tok, gate_up, gate_up, down, out


def _fused_swiglu_gemv_kernel(
    row_ref,  # (S,) int32 scalar prefetch: row of each grid step
    w_row_ref,  # (S,) int32 scalar prefetch: weight row of each grid step
    tok_ref,  # (1, bk)
    wg_ref,  # (1, bk, bf)
    wu_ref,  # (1, bk, bf)
    wd_ref,  # (1, bf, N)
    zeros_ref,  # (S, 1, N) in HBM, aliased to the output
    out_ref,  # (1, N)
    gate_acc_ref,  # (1, bf) fp32
    up_acc_ref,  # (1, bf) fp32
    out_acc_ref,  # (1, N) fp32
    *,
    n_k_tiles: int,
    n_f_tiles: int,
):
    del row_ref, w_row_ref, zeros_ref
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init_gate_up():
        gate_acc_ref[...] = jnp.zeros_like(gate_acc_ref)
        up_acc_ref[...] = jnp.zeros_like(up_acc_ref)

    @pl.when((j == 0) & (k == 0))
    def _init_out():
        out_acc_ref[...] = jnp.zeros_like(out_acc_ref)

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

    @pl.when(k == n_k_tiles - 1)
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
        out_ref[...] = out_acc_ref[...].astype(out_ref.dtype)


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

    Per valid token i: ``out[i] = swiglu(tokens[i]; wg/wu/wd[expert_ids[i]])``
    — each row's expert weights are streamed from HBM exactly once; an
    invalid row is not visited (:func:`gemv_fetch_tables`) and reads zero.

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
    n_live, row, w_row = gemv_fetch_tables(expert_ids, valid)
    tok_map, wg_map, wu_map, wd_map, out_map = gemv_index_maps()

    def run(n_live, row, w_row, tokens, wg, wu, wd, zeros):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_live, f_tiles, k_tiles),
            in_specs=[
                pl.BlockSpec((None, 1, bk), tok_map),
                pl.BlockSpec((1, bk, bf), wg_map),
                pl.BlockSpec((1, bk, bf), wu_map),
                pl.BlockSpec((1, bf, N), wd_map),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, 1, N), out_map),
            scratch_shapes=[
                pltpu.VMEM((1, bf), jnp.float32),
                pltpu.VMEM((1, bf), jnp.float32),
                pltpu.VMEM((1, N), jnp.float32),
            ],
        )
        kernel = functools.partial(
            _fused_swiglu_gemv_kernel, n_k_tiles=k_tiles, n_f_tiles=f_tiles
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, 1, N), tokens.dtype),
            # operand 6: the zero buffer, after the two tables and the
            # four arrays
            input_output_aliases={6: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(row, w_row, tokens, wg, wu, wd, zeros)

    out = _skip_if_idle(
        "swiglu_gemv", run, n_live, row, w_row, tokens.reshape(S, 1, K), wg,
        wu, wd, out_shape=(S, 1, N), dtype=tokens.dtype,
    )
    return out.reshape(S, N)
