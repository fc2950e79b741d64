"""Streaming few-token expert GEMV Pallas kernel — the TPU "PIM path".

The Sieve scheduler sends single-token (and other low arithmetic-intensity)
experts here instead of padding them into 128-row MXU tiles (where a
1-token expert wastes 127/128 of the tile).  The kernel keeps the token
vector resident in VMEM and *streams* the expert's weight tiles from HBM —
the same "broadcast the vector operand, stream the matrix" structure as the
paper's PIM GEMV (§6.2): bandwidth-bound by construction, no MXU padding
waste.

Per token i: out[i] = tokens[i] @ weights[expert_ids[i]] — the weight block
index map reads the scalar-prefetched ``expert_ids``, mirroring how the
paper's custom GPU kernel computes per-GEMV PIM command arguments at
runtime (§6.2 "Issuing PIM Commands").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemv_kernel(
    expert_ids_ref,  # (S,) int32 scalar prefetch
    valid_ref,  # (S,) int32 scalar prefetch (1 = live row)
    tok_ref,  # (1, bk)
    w_ref,  # (1, bk, bn)
    out_ref,  # (1, bn)
    acc_ref,  # (1, bn) fp32
    *,
    n_k_tiles: int,
):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[i] > 0)
    def _compute():
        # (1, bk) x (bk, bn) — VPU/MXU dot on a single row; weight tile
        # streaming dominates (bandwidth-bound, the PIM regime).
        acc_ref[...] += jax.lax.dot_general(
            tok_ref[...],
            w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == n_k_tiles - 1)
    def _finish():
        out_ref[...] = jnp.where(
            valid_ref[i] > 0, acc_ref[...], 0.0
        ).astype(out_ref.dtype)


def expert_gemv(
    tokens: jax.Array,  # (S, K)
    weights: jax.Array,  # (E, K, N)
    expert_ids: jax.Array,  # (S,) int32
    valid: jax.Array,  # (S,) int32
    *,
    bk: int = 512,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    # tokens/output viewed as (S, 1, K) / (S, 1, N) with the row axis
    # squeezed from the block: the TPU tiling rule refuses a (1, bk) block
    # over (S, K) but accepts one whose unit dim is the array's own
    S, K = tokens.shape
    E, _, N = weights.shape
    bk, bn = min(bk, K), min(bn, N)
    assert K % bk == 0 and N % bn == 0, (K, N, bk, bn)
    k_tiles, n_tiles = K // bk, N // bn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, n_tiles, k_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, bk), lambda i, j, k, e, v: (i, 0, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, e, v: (e[i], k, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bn), lambda i, j, k, e, v: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
    )
    kernel = functools.partial(_gemv_kernel, n_k_tiles=k_tiles)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, N), tokens.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(expert_ids, valid, tokens.reshape(S, 1, K), weights)
    return out.reshape(S, N)
