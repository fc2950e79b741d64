"""Decode-attention kernel sweeps vs pure-jnp oracles (interpret mode on
CPU); the expert kernels are in tests/test_fused_swiglu.py."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


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
