"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )


class TestGroupedGemmCapacity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "E,C,K,N,bm", [(4, 16, 64, 96, 8), (8, 8, 128, 128, 8), (2, 32, 32, 64, 16)]
    )
    def test_against_oracle(self, dtype, E, C, K, N, bm):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        buf = jax.random.normal(ks[0], (E, C, K), dtype)
        rhs = jax.random.normal(ks[1], (E, K, N), dtype)
        sizes = jax.random.randint(ks[2], (E,), 0, C + 1)
        out = ops.gmm_capacity(buf, rhs, sizes, bm=bm, bk=32, bn=32, interpret=True)
        exp = ref.grouped_gemm_ref(buf.reshape(E * C, K), rhs, sizes, C)
        np.testing.assert_allclose(
            np.asarray(out.reshape(E * C, N), np.float32),
            np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    def test_empty_groups_produce_zeros(self):
        E, C, K, N = 3, 8, 32, 32
        buf = jnp.ones((E, C, K))
        rhs = jnp.ones((E, K, N))
        sizes = jnp.array([0, 8, 0])
        out = ops.gmm_capacity(buf, rhs, sizes, bm=8, bk=32, bn=32, interpret=True)
        assert float(jnp.abs(out[0]).max()) == 0.0
        assert float(jnp.abs(out[2]).max()) == 0.0
        assert float(jnp.abs(out[1]).max()) > 0.0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_groups_empty(self, dtype):
        """Degenerate ragged case: every expert idle -> all-zero output."""
        E, C, K, N = 4, 8, 32, 32
        buf = jnp.ones((E, C, K), dtype)
        rhs = jnp.ones((E, K, N), dtype)
        out = ops.gmm_capacity(
            buf, rhs, jnp.zeros((E,), jnp.int32), bm=8, bk=32, bn=32,
            interpret=True,
        )
        assert float(jnp.abs(out).max()) == 0.0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_rows_one_expert(self, dtype):
        """The other ragged extreme: one expert owns every live row."""
        E, C, K, N = 4, 16, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        buf = jax.random.normal(ks[0], (E, C, K), dtype)
        rhs = jax.random.normal(ks[1], (E, K, N), dtype)
        sizes = jnp.zeros((E,), jnp.int32).at[2].set(C)
        out = ops.gmm_capacity(buf, rhs, sizes, bm=8, bk=32, bn=32, interpret=True)
        exp = ref.grouped_gemm_ref(buf.reshape(E * C, K), rhs, sizes, C)
        np.testing.assert_allclose(
            np.asarray(out.reshape(E * C, N), np.float32),
            np.asarray(exp, np.float32),
            **_tol(dtype),
        )

    @pytest.mark.parametrize("C", [4, 12, 20, 100])
    def test_bm_clamp_small_capacity(self, C):
        """Regression (ops.py clamp): C < 128 with the default bm used to
        produce a non-sublane-aligned block size (e.g. bm=12)."""
        E, K, N = 3, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        buf = jax.random.normal(ks[0], (E, C, K))
        rhs = jax.random.normal(ks[1], (E, K, N))
        sizes = jax.random.randint(ks[2], (E,), 0, C + 1)
        out = ops.gmm_capacity(buf, rhs, sizes, bk=32, bn=32, interpret=True)
        exp = ref.grouped_gemm_ref(buf.reshape(E * C, K), rhs, sizes, C)
        np.testing.assert_allclose(
            np.asarray(out.reshape(E * C, N)), np.asarray(exp),
            rtol=1e-5, atol=1e-5,
        )

    def test_clamp_bm_is_sublane_aligned(self):
        for bm in (8, 16, 128):
            for rows in (1, 4, 7, 8, 12, 100, 128, 1000):
                got = ops._clamp_bm(bm, rows)
                assert got % ops._SUBLANE == 0 and got >= ops._SUBLANE

    def test_default_blocks_fit_nonpow2_dims(self):
        """Regression: qwen3-class dims (d_expert=768) with the default
        bk=512 used to trip the K % bk assert in grouped_gemm."""
        assert ops._fit_block(512, 768) == 256
        assert ops._fit_block(512, 512) == 512
        assert ops._fit_block(128, 96) == 96
        E, C, K, N = 2, 8, 768, 128
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        buf = jax.random.normal(ks[0], (E, C, K))
        rhs = jax.random.normal(ks[1], (E, K, N))
        sizes = jax.random.randint(ks[2], (E,), 0, C + 1)
        out = ops.gmm_capacity(buf, rhs, sizes, interpret=True)  # defaults
        exp = ref.grouped_gemm_ref(buf.reshape(E * C, K), rhs, sizes, C)
        np.testing.assert_allclose(
            np.asarray(out.reshape(E * C, N)), np.asarray(exp),
            rtol=1e-4, atol=1e-4,
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_rhs_of_group_shared_weights(self, dtype):
        """Segmented EP layout: several ragged groups share one expert's
        weights through the prefetched rhs_of_group table."""
        E, S, C, K, N = 3, 2, 8, 32, 32
        G = E * S
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        buf = jax.random.normal(ks[0], (G, C, K), dtype)
        rhs = jax.random.normal(ks[1], (E, K, N), dtype)
        sizes = jax.random.randint(ks[2], (G,), 0, C + 1)
        rog = jnp.repeat(jnp.arange(E, dtype=jnp.int32), S)
        out = ops.gmm_capacity(
            buf, rhs, sizes, bm=8, bk=32, bn=32, interpret=True,
            rhs_of_group=rog,
        )
        exp = ref.grouped_gemm_ref(buf.reshape(G * C, K), rhs[rog], sizes, C)
        np.testing.assert_allclose(
            np.asarray(out.reshape(G * C, N), np.float32),
            np.asarray(exp, np.float32),
            **_tol(dtype),
        )


class TestGroupedGemmRagged:
    @given(
        sizes=st.lists(st.integers(min_value=0, max_value=20), min_size=2, max_size=6),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=12, deadline=None)
    def test_ragged_random_groups(self, sizes, seed):
        bm, K, N = 8, 32, 32
        E = len(sizes)
        sizes = jnp.asarray(sizes, jnp.int32)
        padded = ((sizes + bm - 1) // bm) * bm
        M = max(int(padded.sum()), bm)
        if int(padded.sum()) == 0:
            return
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        lhs = jax.random.normal(ks[0], (int(padded.sum()), K), jnp.float32)
        rhs = jax.random.normal(ks[1], (E, K, N), jnp.float32)
        out = ops.gmm_ragged(lhs, rhs, sizes, bm=bm, bk=32, bn=32, interpret=True)
        starts = np.concatenate([[0], np.cumsum(np.asarray(padded))[:-1]])
        exp = np.zeros((lhs.shape[0], N), np.float32)
        for g in range(E):
            s, sz = int(starts[g]), int(sizes[g])
            exp[s : s + sz] = np.asarray(lhs[s : s + sz] @ rhs[g])
        np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-4)


class TestExpertGemv:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("S,E,K,N", [(5, 4, 64, 96), (16, 8, 128, 64), (1, 2, 32, 32)])
    def test_against_oracle(self, dtype, S, E, K, N):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        toks = jax.random.normal(ks[0], (S, K), dtype)
        w = jax.random.normal(ks[1], (E, K, N), dtype)
        eids = jax.random.randint(ks[2], (S,), 0, E)
        valid = jnp.ones((S,), jnp.int32).at[0].set(0) if S > 2 else jnp.ones((S,), jnp.int32)
        out = ops.expert_gemv(toks, w, eids, valid, bk=32, bn=32, interpret=True)
        exp = ref.expert_gemv_ref(toks, w, eids, valid)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(dtype)
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_tokens_one_expert(self, dtype):
        S, E, K, N = 12, 4, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(8), 2)
        toks = jax.random.normal(ks[0], (S, K), dtype)
        w = jax.random.normal(ks[1], (E, K, N), dtype)
        eids = jnp.full((S,), 1, jnp.int32)
        out = ops.expert_gemv(toks, w, eids, None, bk=32, bn=32, interpret=True)
        exp = ref.expert_gemv_ref(toks, w, eids, jnp.ones((S,), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(dtype)
        )

    def test_all_rows_invalid_produce_zeros(self):
        S, E, K, N = 6, 3, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(9), 2)
        toks = jax.random.normal(ks[0], (S, K))
        w = jax.random.normal(ks[1], (E, K, N))
        eids = jnp.zeros((S,), jnp.int32)
        out = ops.expert_gemv(
            toks, w, eids, jnp.zeros((S,), jnp.int32), bk=32, bn=32,
            interpret=True,
        )
        assert float(jnp.abs(out).max()) == 0.0

    def test_matches_grouped_gemm_for_single_token_experts(self):
        """The Sieve dual-path invariant: GEMV path == grouped path for
        1-token experts (same math, different kernel)."""
        E, K, N = 4, 64, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 2)
        toks = jax.random.normal(ks[0], (E, K), jnp.float32)
        w = jax.random.normal(ks[1], (E, K, N), jnp.float32)
        eids = jnp.arange(E, dtype=jnp.int32)
        gemv = ops.expert_gemv(toks, w, eids, None, bk=32, bn=32, interpret=True)
        buf = toks[:, None, :]  # (E, C=1, K)
        gmm = ops.gmm_capacity(buf, w, jnp.ones(E, jnp.int32), bm=8, bk=32, bn=32,
                               interpret=True)[:, 0]
        np.testing.assert_allclose(np.asarray(gemv), np.asarray(gmm), rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,Kv,dh,T,bt", [
        (2, 8, 2, 32, 64, 16),
        (3, 4, 4, 64, 48, 16),   # MHA (G=1)
        (1, 16, 2, 16, 128, 32),
    ])
    def test_against_oracle(self, dtype, B, H, Kv, dh, T, bt):
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, H, dh), dtype)
        ck = jax.random.normal(ks[1], (B, Kv, T, dh), dtype)
        cv = jax.random.normal(ks[2], (B, Kv, T, dh), dtype)
        lens = jax.random.randint(ks[3], (B,), 1, T + 1)
        out = ops.decode_attention(q, ck, cv, lens, bt=bt, interpret=True)
        exp = ref.decode_attention_ref(q, ck, cv, lens)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
        )

    def test_length_masking(self):
        """Entries beyond `lengths` must not affect the output."""
        B, H, Kv, dh, T = 1, 4, 2, 16, 32
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (B, H, dh))
        ck = jax.random.normal(ks[1], (B, Kv, T, dh))
        cv = jax.random.normal(ks[2], (B, Kv, T, dh))
        lens = jnp.array([7])
        out1 = ops.decode_attention(q, ck, cv, lens, bt=8, interpret=True)
        ck2 = ck.at[:, :, 7:].set(99.0)
        cv2 = cv.at[:, :, 7:].set(-99.0)
        out2 = ops.decode_attention(q, ck2, cv2, lens, bt=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)

    def test_zero_length_rows_emit_zeros(self):
        """Regression: a fully-masked first tile used to leave ``m_new`` at
        NEG_INF, making ``p = exp(s - m_new) = 1`` everywhere — a uniform
        mean over garbage V rows.  Length-0 slots must emit exact zeros
        (and never NaN), not whatever the padding rows contain."""
        B, H, Kv, dh, T = 3, 4, 2, 16, 32
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (B, H, dh))
        ck = jax.random.normal(ks[1], (B, Kv, T, dh))
        cv = jax.random.normal(ks[2], (B, Kv, T, dh))
        # poison the padding-slot rows with extreme values
        ck = ck.at[0].set(1e4)
        cv = cv.at[0].set(-1e4)
        lens = jnp.array([0, 5, 0])
        for n_splits in (1, 2):
            out = np.asarray(
                ops.decode_attention(
                    q, ck, cv, lens, bt=8, n_splits=n_splits, interpret=True
                )
            )
            assert not np.isnan(out).any()
            np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
            np.testing.assert_array_equal(out[2], np.zeros_like(out[2]))
            # the live row still matches the oracle
            exp = np.asarray(ref.decode_attention_ref(q, ck, cv, lens))
            np.testing.assert_allclose(out[1], exp[1], rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("T,bt", [(48, 32), (96, 64), (768, 512)])
    def test_ragged_tail_tile(self, T, bt):
        """Regression: ``T % bt != 0`` used to trip an assert; the partial
        tail tile is now masked in-kernel (no padded cache copy)."""
        B, H, Kv, dh = 2, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        q = jax.random.normal(ks[0], (B, H, dh))
        ck = jax.random.normal(ks[1], (B, Kv, T, dh))
        cv = jax.random.normal(ks[2], (B, Kv, T, dh))
        lens = jnp.array([T, T - 3])  # lengths reaching into the ragged tail
        out = ops.decode_attention(q, ck, cv, lens, bt=bt, interpret=True)
        exp = ref.decode_attention_ref(q, ck, cv, lens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("n_splits", [2, 3, 8])
    def test_split_kv_lse_combine(self, n_splits):
        """Split-KV partials recombined by LSE must equal the one-pass
        kernel/oracle for mixed lengths (including splits with no live
        positions)."""
        B, H, Kv, dh, T = 4, 8, 4, 32, 96
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        q = jax.random.normal(ks[0], (B, H, dh))
        ck = jax.random.normal(ks[1], (B, Kv, T, dh))
        cv = jax.random.normal(ks[2], (B, Kv, T, dh))
        lens = jnp.array([1, 17, 64, 96])
        out = ops.decode_attention(
            q, ck, cv, lens, bt=16, n_splits=n_splits, interpret=True
        )
        exp = ref.decode_attention_ref(q, ck, cv, lens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4
        )


class TestDecodeAttentionPaged:
    def _build_pool(self, key, B, nb, page, Kv, dh, lens):
        """Allocate ceil(len/page) blocks per slot from a shuffled pool,
        leaving block 0 as trash and poisoning free blocks."""
        n_pool = B * nb + 1
        ks = jax.random.split(key, 3)
        pool_k = jax.random.normal(ks[0], (n_pool, Kv, page, dh))
        pool_v = jax.random.normal(ks[1], (n_pool, Kv, page, dh))
        order = np.asarray(
            jax.random.permutation(ks[2], np.arange(1, n_pool))
        )
        tab = np.zeros((B, nb), np.int32)
        nxt = 0
        for b in range(B):
            need = -(-int(lens[b]) // page)
            for j in range(need):
                tab[b, j] = order[nxt]
                nxt += 1
        return pool_k, pool_v, jnp.asarray(tab)

    def test_against_paged_oracle_and_dense(self):
        B, H, Kv, dh, page, nb = 4, 8, 2, 32, 8, 4
        lens = jnp.array([0, 5, 8, 29])  # empty, partial, boundary, multi-block
        ks = jax.random.split(jax.random.PRNGKey(8), 2)
        q = jax.random.normal(ks[0], (B, H, dh))
        pool_k, pool_v, tab = self._build_pool(ks[1], B, nb, page, Kv, dh, lens)
        out = np.asarray(
            ops.decode_attention_paged(q, pool_k, pool_v, tab, lens, interpret=True)
        )
        exp = np.asarray(
            ref.decode_attention_paged_ref(q, pool_k, pool_v, tab, lens)
        )
        # live rows match the gather oracle; the empty row is exact zeros
        # (the oracle's softmax gives a uniform mean there instead)
        np.testing.assert_allclose(out[1:], exp[1:], rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
        # and the dense kernel agrees on the gathered cache
        ck = ref.gather_pages(pool_k, tab)
        cv = ref.gather_pages(pool_v, tab)
        dense = np.asarray(
            ops.decode_attention(q, ck, cv, lens, bt=page, interpret=True)
        )
        np.testing.assert_allclose(out, dense, rtol=1e-5, atol=1e-5)
