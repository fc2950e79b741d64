"""Fused single-pass SwiGLU kernels + sort-free dispatch equivalence.

Three layers of pinning, per the equivalence-suite style of
tests/test_kernels.py / tests/test_moe_dual.py:

* kernel level — the fused grouped SwiGLU (`ops.swiglu_gmm_capacity`) and
  the fused tail GEMV (`ops.swiglu_gemv`), the only Pallas expert FFN,
  against the dense einsum oracles (`ref.fused_swiglu_gmm_ref` /
  `ref.fused_swiglu_gemv_ref`), in f32 (tight) and bf16 (tolerance),
  across ragged extremes, the `rhs_of_group` segmented layout and the
  block-size helpers, all under interpret mode;
* model level — `experts_ffn_dual` with the Pallas backend against the
  XLA ragged twin and the dense oracle; an EP subprocess case forces the
  kernels through `moe_block`;
* dispatch — the sort-free counting-scatter `dispatch` bit-identical
  (`buf`, `slot_of`, `n_dropped`) to the stable-argsort
  `dispatch_argsort` under hypothesis, including the EP offset/local
  masking path.
"""

import dataclasses
import itertools
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_arch
from repro.kernels import fused_swiglu, ops, ref
from repro.models.moe import (
    RouterOut,
    capacity,
    dispatch,
    dispatch_argsort,
    experts_ffn,
    experts_ffn_dual,
    experts_ffn_dual_segmented,
    init_moe,
    moe_local,
    route,
)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )


def _weights(key, E, K, F, N, dtype):
    ks = jax.random.split(key, 3)
    return (
        (jax.random.normal(ks[0], (E, K, F)) * 0.1).astype(dtype),
        (jax.random.normal(ks[1], (E, K, F)) * 0.1).astype(dtype),
        (jax.random.normal(ks[2], (E, F, N)) * 0.1).astype(dtype),
    )


class TestFusedSwigluGmm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "E,C,K,F,N,bm",
        [
            (4, 16, 64, 96, 64, 8),
            (8, 8, 128, 64, 128, 8),
            (2, 20, 32, 32, 64, 8),
            (4, 16, 64, 96, 96, 8),
            (8, 8, 128, 128, 128, 8),
            (2, 32, 32, 64, 64, 16),
        ],
    )
    def test_against_dense_oracle(self, dtype, E, C, K, F, N, bm):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        buf = jax.random.normal(ks[0], (E, C, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        sizes = jax.random.randint(jax.random.PRNGKey(1), (E,), 0, C + 1)
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bm=bm, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_against_three_call(self, dtype):
        """A capacity that is not a multiple of bm (C = 12, padded to 16
        inside the wrapper, then cut back): equal to the oracle's gate,
        up and down projections, with a full, an empty, a partial and a
        one-row group."""
        E, C, K, F, N = 4, 12, 64, 64, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 2)
        buf = jax.random.normal(ks[0], (E, C, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        sizes = jnp.asarray([12, 0, 5, 1], jnp.int32)
        fused = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bm=8, bk=32, bf=32, interpret=True
        )
        assert fused.shape == (E, C, N)
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(fused, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    @pytest.mark.parametrize("bn", [32, 16])
    def test_blocked_output_accumulator(self, bn):
        """Blocking the d_model output axis (fp32 accumulator (bm, bn)
        instead of the full (bm, d_model) — the large-d_model VMEM fix)
        must match the unblocked single-n-tile schedule.

        Each output element sums the same products over the same f-tiles
        either way, but the tiling changes the shape of every dot, and a
        matmul backend may order a dot's float32 sums differently for
        another shape (on CPU, bn=16 differs from bn=64 by 2 ulp of the
        largest output).  So the tilings agree to 16 float32 ulps of the
        output's largest magnitude; a wrong tile or column offset is an
        O(1) error."""
        E, C, K, F, N = 4, 16, 64, 96, 64
        ks = jax.random.split(jax.random.PRNGKey(11), 2)
        buf = jax.random.normal(ks[0], (E, C, K))
        wg, wu, wd = _weights(ks[1], E, K, F, N, jnp.float32)
        sizes = jnp.asarray([16, 0, 7, 1], jnp.int32)
        full = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bm=8, bk=32, bf=32, bn=N, interpret=True
        )
        blocked = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bm=8, bk=32, bf=32, bn=bn, interpret=True
        )
        full, blocked = np.asarray(full), np.asarray(blocked)
        ulps = 16 * np.finfo(np.float32).eps * np.abs(full).max()
        np.testing.assert_allclose(blocked, full, rtol=0, atol=ulps)
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(blocked), np.asarray(exp), **_tol(jnp.float32)
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_empty_groups_produce_zeros(self, dtype):
        E, C, K, F, N = 3, 8, 32, 32, 32
        buf = jnp.ones((E, C, K), dtype)
        wg, wu, wd = _weights(jax.random.PRNGKey(3), E, K, F, N, dtype)
        sizes = jnp.array([0, 8, 0])
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bm=8, bk=32, bf=32, interpret=True
        )
        assert float(jnp.abs(out[0]).max()) == 0.0
        assert float(jnp.abs(out[2]).max()) == 0.0
        assert float(jnp.abs(out[1]).max()) > 0.0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_groups_empty(self, dtype):
        """Every expert idle -> all-zero output."""
        E, C, K, F, N = 4, 8, 32, 32, 32
        buf = jnp.ones((E, C, K), dtype)
        wg, wu, wd = _weights(jax.random.PRNGKey(4), E, K, F, N, dtype)
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, jnp.zeros((E,), jnp.int32), bm=8, bk=32, bf=32,
            interpret=True,
        )
        assert float(jnp.abs(out).max()) == 0.0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_rows_one_expert(self, dtype):
        """The other ragged extreme: one expert owns every live row."""
        E, C, K, F, N = 4, 16, 32, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        buf = jax.random.normal(ks[0], (E, C, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        sizes = jnp.zeros((E,), jnp.int32).at[2].set(C)
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bm=8, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_rhs_of_group_shared_weights(self, dtype):
        """Segmented EP layout: several ragged groups share one expert's
        weight triple through the prefetched rhs_of_group table."""
        E, S, C, K, F, N = 3, 2, 8, 32, 32, 32
        G = E * S
        ks = jax.random.split(jax.random.PRNGKey(6), 2)
        buf = jax.random.normal(ks[0], (G, C, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        sizes = jax.random.randint(jax.random.PRNGKey(7), (G,), 0, C + 1)
        rog = jnp.repeat(jnp.arange(E, dtype=jnp.int32), S)
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, rhs_of_group=rog, bm=8, bk=32, bf=32,
            interpret=True,
        )
        exp = ref.fused_swiglu_gmm_ref(
            buf, wg, wu, wd, sizes, rhs_of_group=rog
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    def test_nonpow2_expert_dim_default_blocks(self):
        """qwen3-class d_expert=768 with default block sizes (the
        _fit_block regression surface, now for the fused kernel)."""
        E, C, K, F = 2, 8, 256, 768
        ks = jax.random.split(jax.random.PRNGKey(8), 2)
        buf = jax.random.normal(ks[0], (E, C, K))
        wg, wu, wd = _weights(ks[1], E, K, F, K, jnp.float32)
        sizes = jnp.asarray([5, 2], jnp.int32)
        out = ops.swiglu_gmm_capacity(buf, wg, wu, wd, sizes, interpret=True)
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("C", [4, 12, 20, 100])
    def test_bm_clamp_small_capacity(self, C):
        """Regression (ops._clamp_bm): C < 128 with the default bm used to
        produce a non-sublane-aligned block size (e.g. bm=12)."""
        E, K, F, N = 3, 32, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        buf = jax.random.normal(ks[0], (E, C, K))
        wg, wu, wd = _weights(ks[1], E, K, F, N, jnp.float32)
        sizes = jax.random.randint(ks[2], (E,), 0, C + 1)
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5
        )

    def test_clamp_bm_is_sublane_aligned(self):
        for bm in (8, 16, 128):
            for rows in (1, 4, 7, 8, 12, 100, 128, 1000):
                got = ops._clamp_bm(bm, rows)
                assert got % ops._SUBLANE == 0 and got >= ops._SUBLANE

    def test_default_blocks_fit_nonpow2_dims(self):
        """Regression: qwen3-class dims (768) with the default bk=512 used
        to leave a k block that does not divide the dim; here d_model is
        768, so the k and output blocks both meet it."""
        assert ops._fit_block(512, 768) == 256
        assert ops._fit_block(512, 512) == 512
        assert ops._fit_block(128, 96) == 96
        E, C, K, F = 2, 8, 768, 128
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        buf = jax.random.normal(ks[0], (E, C, K))
        wg, wu, wd = _weights(ks[1], E, K, F, K, jnp.float32)
        sizes = jax.random.randint(ks[2], (E,), 0, C + 1)
        out = ops.swiglu_gmm_capacity(buf, wg, wu, wd, sizes, interpret=True)
        exp = ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4
        )


class TestFusedSwigluGemv:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("S,E,K,F,N", [(5, 4, 64, 96, 64), (16, 8, 128, 64, 128), (1, 2, 32, 32, 32)])
    def test_against_oracle(self, dtype, S, E, K, F, N):
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        toks = jax.random.normal(ks[0], (S, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        eids = jax.random.randint(ks[2], (S,), 0, E)
        valid = (
            jnp.ones((S,), jnp.int32).at[0].set(0)
            if S > 2
            else jnp.ones((S,), jnp.int32)
        )
        out = ops.swiglu_gemv(
            toks, wg, wu, wd, eids, valid, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gemv_ref(toks, wg, wu, wd, eids, valid)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    def test_against_three_call(self):
        """Rows of one expert spread over the call with an invalid row in
        the middle: equal to the oracle's gate, up and down projections."""
        S, E, K, F, N = 9, 4, 64, 64, 64
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        toks = jax.random.normal(ks[0], (S, K))
        wg, wu, wd = _weights(ks[1], E, K, F, N, jnp.float32)
        eids = jax.random.randint(ks[2], (S,), 0, E)
        valid = jnp.ones((S,), jnp.int32).at[3].set(0)
        fused = ops.swiglu_gemv(
            toks, wg, wu, wd, eids, valid, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gemv_ref(toks, wg, wu, wd, eids, valid)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(exp), rtol=1e-5, atol=1e-5
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_tokens_one_expert(self, dtype):
        S, E, K, F, N = 12, 4, 64, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(8), 2)
        toks = jax.random.normal(ks[0], (S, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        eids = jnp.full((S,), 1, jnp.int32)
        out = ops.swiglu_gemv(
            toks, wg, wu, wd, eids, None, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gemv_ref(
            toks, wg, wu, wd, eids, jnp.ones((S,), jnp.int32)
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_zero_tail_all_rows_invalid(self, dtype):
        """The zero-tail ragged extreme: every row masked -> all zeros."""
        S, E, K, F, N = 6, 3, 32, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(12), 2)
        toks = jax.random.normal(ks[0], (S, K), dtype)
        wg, wu, wd = _weights(ks[1], E, K, F, N, dtype)
        out = ops.swiglu_gemv(
            toks, wg, wu, wd, jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32), bk=32, bf=32, interpret=True,
        )
        assert float(jnp.abs(out).max()) == 0.0

    def test_matches_fused_gmm_for_single_token_experts(self):
        """Dual-path invariant carried to the fused kernels: fused GEMV ==
        fused grouped path for 1-token experts."""
        E, K, F, N = 4, 64, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(13), 2)
        toks = jax.random.normal(ks[0], (E, K))
        wg, wu, wd = _weights(ks[1], E, K, F, N, jnp.float32)
        eids = jnp.arange(E, dtype=jnp.int32)
        gemv = ops.swiglu_gemv(
            toks, wg, wu, wd, eids, None, bk=32, bf=32, interpret=True
        )
        gmm = ops.swiglu_gmm_capacity(
            toks[:, None, :], wg, wu, wd, jnp.ones(E, jnp.int32),
            bm=8, bk=32, bf=32, interpret=True,
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(gemv), np.asarray(gmm), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# Live-only streaming: a dead group or row costs no grid step and no copy
# ---------------------------------------------------------------------------

# which of six experts have rows
LIVENESS = {
    "all_dead": [0, 0, 0, 0, 0, 0],
    "all_live": [1, 1, 1, 1, 1, 1],
    "leading_dead": [0, 0, 1, 1, 1, 1],
    "trailing_dead": [1, 1, 1, 1, 0, 0],
    "single_live": [0, 0, 0, 1, 0, 0],
    "alternating": [1, 0, 1, 0, 1, 0],
}
# rows of each expert where it is live: with C = 20 and bm = 8 a group
# spans three m-tiles, so 1 and 8 leave later tiles of a live group dead
LIVE_ROWS = [20, 9, 1, 16, 8, 17]


def _stacked_weights(key, L, E, K, F, N, dtype):
    """Layer-stacked ``(L*E, ...)`` weights, as the decode program hands
    the kernels."""
    return _weights(key, L * E, K, F, N, dtype)


class TestLiveOnlyStreaming:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("layer", [0, 1])
    @pytest.mark.parametrize("pattern", sorted(LIVENESS))
    def test_gmm_against_oracle(self, pattern, layer, dtype):
        """Head kernel at each liveness pattern, groups spanning three
        m-tiles, weights read from layer ``layer`` of a two-layer stack
        at row ``layer*E + e``: equal to the einsum oracle, and dead
        groups and padding rows exactly zero."""
        L, E, C, K, F, N = 2, 6, 20, 64, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(20), 2)
        buf = jax.random.normal(ks[0], (E, C, K), dtype)
        wg, wu, wd = _stacked_weights(ks[1], L, E, K, F, N, dtype)
        sizes = jnp.asarray(LIVE_ROWS, jnp.int32) * jnp.asarray(LIVENESS[pattern])
        rog = layer * E + jnp.arange(E, dtype=jnp.int32)
        out = ops.swiglu_gmm_capacity(
            buf, wg, wu, wd, sizes, rhs_of_group=rog, bm=8, bk=32, bf=32,
            interpret=True,
        )
        sl = slice(layer * E, (layer + 1) * E)
        exp = ref.fused_swiglu_gmm_ref(buf, wg[sl], wu[sl], wd[sl], sizes)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )
        dead = np.arange(C)[None, :] >= np.asarray(sizes)[:, None]
        assert np.all(np.asarray(out, np.float32)[dead] == 0.0)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("tau", [1, 2])
    @pytest.mark.parametrize("pattern", sorted(LIVENESS))
    def test_gemv_against_oracle(self, pattern, tau, dtype):
        """Tail kernel at each liveness pattern, ``tau`` rows an expert
        (the tail slab's layout), weights read from the second layer of a
        two-layer stack: equal to the einsum oracle, invalid rows exactly
        zero."""
        L, E, K, F, N = 2, 6, 64, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(21), 2)
        toks = jax.random.normal(ks[0], (E * tau, K), dtype)
        wg, wu, wd = _stacked_weights(ks[1], L, E, K, F, N, dtype)
        eids = E + jnp.repeat(jnp.arange(E, dtype=jnp.int32), tau)
        live = jnp.repeat(jnp.asarray(LIVENESS[pattern], jnp.int32), tau)
        valid = live.at[1::2].set(0) if tau > 1 else live  # a dead row of a live expert
        out = ops.swiglu_gemv(
            toks, wg, wu, wd, eids, valid, bk=32, bf=32, interpret=True
        )
        exp = ref.fused_swiglu_gemv_ref(toks, wg, wu, wd, eids, valid)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **_tol(dtype),
        )
        assert np.all(np.asarray(out, np.float32)[np.asarray(valid) == 0] == 0.0)


def _block_fetches(maps, grid, tables):
    """Weight blocks the TPU pipeline copies in: it issues a copy for an
    operand only where the block index differs from the previous grid
    step's.  Per map, the count of such changes over the grid in
    row-major order, and the weight rows the fetched blocks belong to."""
    tables = [np.asarray(t) for t in tables]
    count, rows, last = [0] * len(maps), set(), [None] * len(maps)
    for idx in itertools.product(*(range(int(g)) for g in grid)):
        for m, fn in enumerate(maps):
            block = tuple(int(b) for b in fn(*idx, *tables))
            if block != last[m]:
                count[m] += 1
                rows.add(block[0])
                last[m] = block
    return count, rows


# (E, C, d_model, d_expert): qwen3-30b-a3b decode at 48 slots, and the
# DeepSeek-V2 share (20 held experts) at 128 slots
WIDTHS = {"qwen3": (128, 48, 2048, 768), "deepseek_share": (20, 128, 5120, 1536)}


class TestWeightFetches:
    """CPU-checkable statement that a dead expert costs no weight copy:
    over the grid each kernel runs, the weight index maps fed with the
    kernel's fetch tables change block exactly once per block of each
    live expert, and name no other expert's weights."""

    def _live(self, E, C, seed):
        rng = np.random.default_rng(seed)
        hot = rng.choice(E, max(1, E // 8), replace=False)
        sizes = np.zeros(E, np.int32)
        sizes[hot] = rng.integers(2, C + 1, hot.size)
        tail = rng.choice(np.flatnonzero(sizes == 0), E // 6, replace=False)
        valid = np.zeros(E, np.int32)
        valid[tail] = 1
        return sizes, valid

    @pytest.mark.parametrize("blocked_n", [False, True])
    @pytest.mark.parametrize("cell", sorted(WIDTHS))
    def test_head_fetches_each_live_expert_once(self, cell, blocked_n):
        E, C, D, F = WIDTHS[cell]
        sizes, _ = self._live(E, C, seed=1)
        base = 3 * E  # layer 3 of the stacked weights
        bm = ops._clamp_bm(128, C)
        bk, bf = ops._fit_block(512, D), ops._fit_block(256, F)
        bn = D // 4 if blocked_n else ops._fit_acc_bn(bm, D)
        _, group_of_tile, row_in_group, bm, _ = ops._capacity_tiles(
            jnp.zeros((E, C, 1)), bm
        )
        n_live, *tables = fused_swiglu.gmm_fetch_tables(
            jnp.asarray(sizes), group_of_tile, row_in_group,
            base + jnp.arange(E, dtype=jnp.int32), bm,
        )
        n_t, f_t, k_t = D // bn, F // bf, D // bk
        _, wg_map, wu_map, wd_map, _ = fused_swiglu.gmm_index_maps(f_t, k_t)
        live = int((sizes > 0).sum())
        assert int(n_live) == live  # one m-tile a group: C <= bm
        count, rows = _block_fetches(
            [wg_map, wu_map, wd_map], (n_live, n_t, f_t, k_t), tables
        )
        assert count == [live * f_t * k_t, live * f_t * k_t, live * f_t * n_t]
        assert rows == set((base + np.flatnonzero(sizes)).tolist())

    @pytest.mark.parametrize("cell", sorted(WIDTHS))
    def test_tail_fetches_each_live_expert_once(self, cell):
        E, C, D, F = WIDTHS[cell]
        _, valid = self._live(E, C, seed=2)
        bk, bf = ops._fit_block(512, D), ops._fit_block(256, F)
        n_live, *tables = fused_swiglu.gemv_fetch_tables(
            jnp.arange(E, dtype=jnp.int32), jnp.asarray(valid)
        )
        f_t, k_t = F // bf, D // bk
        _, wg_map, wu_map, wd_map, _ = fused_swiglu.gemv_index_maps()
        live = int(valid.sum())
        assert int(n_live) == live
        count, rows = _block_fetches(
            [wg_map, wu_map, wd_map], (n_live, f_t, k_t), tables
        )
        assert count == [live * f_t * k_t, live * f_t * k_t, live * f_t]
        assert rows == set(np.flatnonzero(valid).tolist())

    def test_nothing_live_runs_no_step(self):
        """With no live group or row the grid bound is 0 (the call then
        returns its zero buffer without issuing the kernel)."""
        E, C = 8, 16
        _, group_of_tile, row_in_group, bm, _ = ops._capacity_tiles(
            jnp.zeros((E, C, 1)), 8
        )
        zero = jnp.zeros((E,), jnp.int32)
        n_head, *_ = fused_swiglu.gmm_fetch_tables(
            zero, group_of_tile, row_in_group, jnp.arange(E, dtype=jnp.int32), bm
        )
        n_tail, *_ = fused_swiglu.gemv_fetch_tables(jnp.arange(E), zero)
        assert int(n_head) == 0 and int(n_tail) == 0


# ---------------------------------------------------------------------------
# Model layer: fused backend through the dual-path executor
# ---------------------------------------------------------------------------


def tiny_arch(cf=8.0, min_cap=64, exec_mode="dual_path", max_head=0, tail=1):
    arch = get_arch("qwen3-moe-30b-a3b").reduced()
    return dataclasses.replace(
        arch,
        moe=dataclasses.replace(
            arch.moe,
            capacity_factor=cf,
            min_capacity=min_cap,
            expert_exec=exec_mode,
            dual_max_head=max_head,
            dual_tail_tokens=tail,
        ),
    )


def routed_params(key, arch, dtype=jnp.float32):
    p = init_moe(key, arch, dtype=dtype)
    return {k: p[k] for k in ("w_router", "w_gate", "w_up", "w_down")}


def _dense(arch):
    return dataclasses.replace(
        arch, moe=dataclasses.replace(arch.moe, expert_exec="dense")
    )


class TestFusedModelLayer:
    @pytest.fixture(autouse=True)
    def _force_pallas(self, monkeypatch):
        monkeypatch.setenv("REPRO_DUAL_BACKEND", "pallas")

    def _disp(self, p, arch, x):
        cfg = arch.moe
        T = x.shape[0]
        r = route(x, p["w_router"], cfg)
        cap = capacity(T, cfg, cfg.n_experts)
        disp = dispatch(x, r, cfg.n_experts, cap)
        rows = jnp.minimum(r.counts, cap)
        return disp, rows

    def test_fused_toggle_matches_three_call(self):
        """The full dual executor on the Pallas kernels, head and tail
        paths both live (tail = experts with 1-2 rows), == the dense
        einsum oracle over the same capacity buffer."""
        arch = tiny_arch(tail=2)
        p = routed_params(jax.random.PRNGKey(0), arch)
        # 12 tokens: five experts take 3-5 rows (head), three take 1-2
        x = jax.random.normal(jax.random.PRNGKey(1), (12, arch.d_model))
        disp, rows = self._disp(p, arch, x)
        rows_np = np.asarray(rows)
        assert (rows_np > 2).any() and ((rows_np > 0) & (rows_np <= 2)).any()
        y_fused, nd_fused = experts_ffn_dual(p, disp.buf, rows, arch.moe)
        y_dense = experts_ffn(p, disp.buf)
        assert int(nd_fused) == 0
        np.testing.assert_allclose(
            np.asarray(y_fused), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )

    def test_fused_pallas_matches_dense_oracle(self):
        arch = tiny_arch()
        p = routed_params(jax.random.PRNGKey(0), arch)
        x = jax.random.normal(jax.random.PRNGKey(2), (16, arch.d_model))
        out_dense = moe_local(p, x, _dense(arch))
        out_dual = moe_local(p, x, arch)  # fused pallas by default
        np.testing.assert_allclose(
            np.asarray(out_dual.y), np.asarray(out_dense.y),
            rtol=1e-5, atol=1e-5,
        )

    def test_fused_pallas_matches_xla_twin(self):
        arch = tiny_arch(max_head=3)
        p = routed_params(jax.random.PRNGKey(0), arch)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, arch.d_model))
        disp, rows = self._disp(p, arch, x)
        y_pal, nd_pal = experts_ffn_dual(
            p, disp.buf, rows, arch.moe, backend="pallas"
        )
        y_xla, nd_xla = experts_ffn_dual(
            p, disp.buf, rows, arch.moe, backend="xla"
        )
        assert int(nd_pal) == int(nd_xla)
        np.testing.assert_allclose(
            np.asarray(y_pal), np.asarray(y_xla), rtol=1e-5, atol=1e-5
        )

    def test_fused_segmented_matches_unfused(self):
        """EP a2a segmented layout through the Pallas kernels
        (rhs_of_group weight sharing + head-budget compaction) == the XLA
        twin of the same executor."""
        rng = np.random.default_rng(0)
        E, S, C, d, f = 4, 2, 4, 16, 8
        cfg = dataclasses.replace(
            tiny_arch().moe, dual_max_head=1, dual_tail_tokens=1
        )
        buf = jnp.asarray(rng.standard_normal((E, S, C, d)), jnp.float32)
        sizes = jnp.asarray([[4, 3], [2, 1], [1, 0], [3, 2]], jnp.int32)
        params = {
            "w_gate": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
            "w_up": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
            "w_down": jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32),
        }
        y_xla, nd_xla = experts_ffn_dual_segmented(
            params, buf, sizes, cfg, backend="xla"
        )
        y_fused, nd_fused = experts_ffn_dual_segmented(params, buf, sizes, cfg)
        assert int(nd_xla) == int(nd_fused)
        np.testing.assert_allclose(
            np.asarray(y_fused), np.asarray(y_xla), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# Sort-free dispatch == stable-argsort dispatch (bit-identical)
# ---------------------------------------------------------------------------


class TestSortFreeDispatch:
    @given(
        T=st.integers(1, 40),
        k=st.integers(1, 4),
        E=st.integers(1, 12),
        cap=st.integers(1, 9),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=25, deadline=None)
    def test_bit_identical_to_argsort(self, T, k, E, cap, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((T, 8)), jnp.float32)
        eidx = jnp.asarray(rng.integers(0, E, size=(T, k)), jnp.int32)
        w = jnp.full((T, k), 1.0 / k, jnp.float32)
        counts = jnp.zeros((E,), jnp.int32).at[eidx.reshape(-1)].add(1)
        r = RouterOut(eidx, w, jnp.zeros(()), counts)
        a = dispatch(x, r, E, cap)
        b = dispatch_argsort(x, r, E, cap)
        np.testing.assert_array_equal(np.asarray(a.buf), np.asarray(b.buf))
        np.testing.assert_array_equal(
            np.asarray(a.slot_of), np.asarray(b.slot_of)
        )
        assert int(a.n_dropped) == int(b.n_dropped)

    @given(
        T=st.integers(1, 24),
        E=st.integers(2, 12),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=15, deadline=None)
    def test_bit_identical_under_ep_offset(self, T, E, seed):
        """The EP shard masking path: remote assignments -> slot -1, no
        drop accounting."""
        rng = np.random.default_rng(seed)
        k, cap = 2, 3
        off = int(rng.integers(0, E))
        n_local = int(rng.integers(1, E + 1))
        x = jnp.asarray(rng.standard_normal((T, 8)), jnp.float32)
        eidx = jnp.asarray(rng.integers(0, E, size=(T, k)), jnp.int32)
        w = jnp.full((T, k), 0.5, jnp.float32)
        counts = jnp.zeros((E,), jnp.int32).at[eidx.reshape(-1)].add(1)
        r = RouterOut(eidx, w, jnp.zeros(()), counts)
        a = dispatch(x, r, E, cap, expert_offset=off, n_local=n_local)
        b = dispatch_argsort(x, r, E, cap, expert_offset=off, n_local=n_local)
        np.testing.assert_array_equal(np.asarray(a.buf), np.asarray(b.buf))
        np.testing.assert_array_equal(
            np.asarray(a.slot_of), np.asarray(b.slot_of)
        )
        assert int(a.n_dropped) == int(b.n_dropped)

    def test_prefill_scale_falls_back_to_argsort(self, monkeypatch):
        """Above the counting-matrix budget the dispatcher must delegate
        to the sort formulation (same outputs either way — the switch is
        purely a trace-time cost choice)."""
        from repro.models import moe as moe_mod

        rng = np.random.default_rng(0)
        T, k, E, cap = 16, 2, 4, 3
        x = jnp.asarray(rng.standard_normal((T, 8)), jnp.float32)
        eidx = jnp.asarray(rng.integers(0, E, size=(T, k)), jnp.int32)
        w = jnp.full((T, k), 0.5, jnp.float32)
        counts = jnp.zeros((E,), jnp.int32).at[eidx.reshape(-1)].add(1)
        r = RouterOut(eidx, w, jnp.zeros(()), counts)
        ref_out = dispatch_argsort(x, r, E, cap)
        monkeypatch.setattr(moe_mod, "_COUNTING_DISPATCH_MAX_ELEMS", 0)
        calls = []
        orig = moe_mod.dispatch_argsort
        monkeypatch.setattr(
            moe_mod, "dispatch_argsort",
            lambda *a, **kw: calls.append(1) or orig(*a, **kw),
        )
        out = moe_mod.dispatch(x, r, E, cap)
        assert calls, "dispatch did not fall back to argsort above budget"
        np.testing.assert_array_equal(
            np.asarray(out.buf), np.asarray(ref_out.buf)
        )

    def test_slot_rank_is_token_order(self):
        """Within an expert, capacity slots fill in token order (what the
        stable sort guaranteed and the running counters preserve)."""
        T, k, E, cap = 6, 1, 2, 8
        x = jnp.asarray(np.arange(T * 4, dtype=np.float32).reshape(T, 4))
        eidx = jnp.asarray([[0], [1], [0], [0], [1], [0]], jnp.int32)
        w = jnp.ones((T, 1), jnp.float32)
        counts = jnp.zeros((E,), jnp.int32).at[eidx.reshape(-1)].add(1)
        r = RouterOut(eidx, w, jnp.zeros(()), counts)
        d = dispatch(x, r, E, cap)
        np.testing.assert_array_equal(
            np.asarray(d.slot_of[:, 0]),
            [0, cap + 0, 1, 2, cap + 1, 3],
        )


# ---------------------------------------------------------------------------
# EP subprocess: fused kernels through moe_block under shard_map
# ---------------------------------------------------------------------------


def _run_subprocess(script: str, marker: str, **env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert marker in r.stdout, r.stderr[-2000:]


_EP_FUSED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.models.moe import init_moe, moe_block, MeshInfo

arch = get_arch("qwen3-moe-30b-a3b").reduced()
arch = dataclasses.replace(arch, moe=dataclasses.replace(
    arch.moe, capacity_factor=8.0, min_capacity=64, expert_exec="dual_path"))
dense = dataclasses.replace(arch, moe=dataclasses.replace(
    arch.moe, expert_exec="dense"))
p = init_moe(jax.random.PRNGKey(0), arch, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, arch.d_model))
from repro.launch.mesh import make_mesh, use_mesh
mesh = make_mesh((1, 4), ("data", "model"))
mi = MeshInfo(mesh=mesh, data_axes=("data",), model_axis="model")
out_local = moe_block(p, x, dense)
with use_mesh(mesh):
    out_ep = jax.jit(lambda p, x: moe_block(p, x, arch, mi))(p, x)
err = float(jnp.max(jnp.abs(out_ep.y - out_local.y)))
assert err < 1e-4, err
print("EP-FUSED-OK")
"""


def test_ep_fused_pallas_matches_local_dense():
    """The fused Pallas kernels (interpret mode) through EP shard_map ==
    the local dense oracle."""
    _run_subprocess(
        _EP_FUSED_SCRIPT, "EP-FUSED-OK",
        REPRO_DUAL_BACKEND="pallas",
    )
