"""Paged KV cache: paged == dense == reference equivalence, block-table
allocator invariants, slot reuse, truncation, and crash consistency."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_arch
from repro.models import LM
from repro.models import attention as attn_lib
from repro.serving import BatchingConfig, PagedKVCache, Request, ServingEngine
import repro.serving.request as reqmod


@pytest.fixture(scope="module")
def lm_and_params():
    arch = get_arch("qwen3-moe-30b-a3b").reduced()
    lm = LM(arch, dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0))


def make_engine(lm, p, paged, **bk):
    reqmod._next_id = 0  # identical req ids across paired engines
    bk.setdefault("n_slots", 4)
    bk.setdefault("max_seq", 64)
    cfg = BatchingConfig(paged=paged, page_size=8, **bk)
    return ServingEngine(lm, p, cfg)


def mixed_requests(n=6, seed=0, new=6):
    rng = np.random.default_rng(seed)
    # mixed sequence lengths incl. page-boundary-straddling prompts
    # (page_size=8): 5, 8, 9, 16, 17, 24 ...
    lens = [5, 8, 9, 16, 17, 24][:n]
    return [
        Request(
            prompt=list(rng.integers(0, 250, size=pl)), max_new_tokens=new
        )
        for pl in lens
    ]


# ---------------------------------------------------------------------------
# Attention-level equivalence (twin vs oracle)
# ---------------------------------------------------------------------------


class TestPagedAttentionTwin:
    def _pool(self, seed, B, nb, page, Kv, dh, lens):
        n_pool = B * nb + 1
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        pool_k = jax.random.normal(ks[0], (n_pool, Kv, page, dh))
        pool_v = jax.random.normal(ks[1], (n_pool, Kv, page, dh))
        tab = np.zeros((B, nb), np.int32)
        owner = np.full((n_pool,), -1, np.int32)
        bpos = np.zeros((n_pool,), np.int32)
        nxt = 1
        for b in range(B):
            for j in range(-(-int(lens[b]) // page)):
                tab[b, j] = nxt
                owner[nxt] = b
                bpos[nxt] = j
                nxt += 1
        return (
            pool_k, pool_v, jnp.asarray(tab), jnp.asarray(owner),
            jnp.asarray(bpos),
        )

    def test_pool_major_twin_matches_gather_oracle(self):
        """The pool-major XLA twin (segment-reduce over physical blocks)
        must match the gather-then-dense oracle at mixed lengths, block
        boundaries, and with free/poisoned blocks in the pool."""
        B, H, Kv, dh, page, nb = 4, 8, 2, 32, 8, 4
        lens = jnp.asarray([3, 8, 17, 32])
        pool_k, pool_v, tab, owner, bpos = self._pool(
            9, B, nb, page, Kv, dh, lens
        )
        # poison every free block — they must be fully masked out
        free = np.asarray(owner) < 0
        pool_k = pool_k.at[np.where(free)[0]].set(1e4)
        pool_v = pool_v.at[np.where(free)[0]].set(-1e4)
        q = jax.random.normal(jax.random.PRNGKey(10), (B, 1, H, dh))
        twin = attn_lib.paged_decode_attention_xla(
            q, pool_k, pool_v, owner, bpos, lens
        )
        exp = attn_lib.paged_decode_attention_ref(
            q, pool_k, pool_v, tab, lens
        )
        np.testing.assert_allclose(
            np.asarray(twin), np.asarray(exp), rtol=1e-4, atol=1e-4
        )

    def test_twin_zero_length_row_is_zeros(self):
        B, H, Kv, dh, page, nb = 2, 4, 2, 16, 8, 2
        lens = jnp.asarray([0, 9])
        pool_k, pool_v, tab, owner, bpos = self._pool(
            11, B, nb, page, Kv, dh, lens
        )
        q = jax.random.normal(jax.random.PRNGKey(12), (B, 1, H, dh))
        twin = np.asarray(
            attn_lib.paged_decode_attention_xla(
                q, pool_k, pool_v, owner, bpos, lens
            )
        )
        assert not np.isnan(twin).any()
        np.testing.assert_array_equal(twin[0], np.zeros_like(twin[0]))


# ---------------------------------------------------------------------------
# Block-table allocator
# ---------------------------------------------------------------------------


class TestPagedKVCacheAllocator:
    def test_trash_block_reserved(self):
        kv = PagedKVCache(BatchingConfig(n_slots=2, max_seq=32, page_size=8))
        assert kv.n_pool == 2 * 4 + 1
        assert kv.n_free == kv.n_pool - 1
        assert PagedKVCache.TRASH not in kv.free_blocks
        assert (kv.block_table == PagedKVCache.TRASH).all()

    def test_exhaustion_raises(self):
        kv = PagedKVCache(
            BatchingConfig(n_slots=2, max_seq=32, page_size=8, pool_blocks=3)
        )
        kv.ensure(0, 16)  # 2 blocks -> pool drained
        with pytest.raises(RuntimeError, match="exhausted"):
            kv.ensure(1, 8)

    @settings(max_examples=30, deadline=None)
    # each op is an int encoding (free?, slot, n_tokens); the compat shim
    # only supports scalar strategies, so ops are packed: bit 0 = free,
    # bits 1-2 = slot, rest = token count
    @given(ops_list=st.lists(st.integers(0, 8 * 41 - 1),
                             min_size=1, max_size=40))
    def test_allocate_free_conservation(self, ops_list):
        """Property: after any interleaving of ensure/free, free + owned ==
        pool - 1 (trash), every owned block is referenced by exactly one
        live table cell, and owner/block_pos agree with the table."""
        kv = PagedKVCache(BatchingConfig(n_slots=4, max_seq=40, page_size=8))
        for op in ops_list:
            slot, n_tokens = (op >> 1) & 3, op >> 3
            if op & 1:
                kv.free_slot(slot)
            else:
                kv.ensure(slot, n_tokens)
        owned = [b for b in range(kv.n_pool) if kv.owner[b] >= 0]
        assert kv.n_free + len(owned) == kv.n_pool - 1
        assert len(set(kv.free_blocks)) == kv.n_free
        assert PagedKVCache.TRASH not in kv.free_blocks
        assert set(kv.free_blocks).isdisjoint(owned)
        for b in owned:
            s, j = int(kv.owner[b]), int(kv.block_pos[b])
            assert int(kv.block_table[s, j]) == b
            assert j < int(kv.slot_blocks[s])
        # live table cells reference owned blocks exactly once
        live = [
            int(kv.block_table[s, j])
            for s in range(kv.n_slots)
            for j in range(int(kv.slot_blocks[s]))
        ]
        assert sorted(live) == sorted(owned)


# ---------------------------------------------------------------------------
# Engine-level equivalence
# ---------------------------------------------------------------------------


class TestPagedEngine:
    def test_paged_matches_dense_tokens(self, lm_and_params):
        """paged == dense on the full serving path: identical generated
        tokens for mixed-length requests (page boundaries crossed both at
        prefill and during decode)."""
        lm, p = lm_and_params
        outs = {}
        for paged in (False, True):
            eng = make_engine(lm, p, paged)
            for r in mixed_requests():
                eng.submit(r)
            eng.run_until_done(max_steps=200)
            outs[paged] = {
                r.req_id: list(r.generated) for r in eng.sched.finished
            }
        assert outs[True] == outs[False]
        assert len(outs[True]) == 6

    def test_slot_reuse_no_stale_block_leakage(self, lm_and_params):
        """A request decoded in a slot whose blocks previously held another
        (longer) request must generate exactly what it generates on a
        fresh engine — freed blocks' stale bytes must never leak through
        the masking."""
        lm, p = lm_and_params
        long_req = mixed_requests(n=6, seed=1, new=8)[5]  # 24-token prompt
        probe = mixed_requests(n=1, seed=2, new=8)[0]  # 5-token prompt

        eng = make_engine(lm, p, True, n_slots=1)
        eng.submit(Request(prompt=list(long_req.prompt), max_new_tokens=8))
        eng.run_until_done(max_steps=100)
        assert eng.paged.n_free == eng.paged.n_pool - 1  # slot 0 freed
        eng.submit(Request(prompt=list(probe.prompt), max_new_tokens=8))
        eng.run_until_done(max_steps=100)
        reused = list(eng.sched.finished[-1].generated)

        fresh = make_engine(lm, p, True, n_slots=1)
        fresh.submit(Request(prompt=list(probe.prompt), max_new_tokens=8))
        fresh.run_until_done(max_steps=100)
        assert list(fresh.sched.finished[-1].generated) == reused

    def test_paged_decode_buffer_donation(self, lm_and_params):
        """The donated-cache contract survives the paged layout: the pool
        buffers are updated in place across decode steps (same device
        pointers), and the pre-step cache handle is consumed."""
        lm, p = lm_and_params
        eng = make_engine(lm, p, True)
        for r in mixed_requests(n=2):
            eng.submit(r)
        eng.step()  # admit + prefill (+ first decode trace)
        eng.step()
        old_leaves = jax.tree.leaves(eng.cache)
        old_ptrs = {leaf.unsafe_buffer_pointer() for leaf in old_leaves}
        eng.step()  # pure decode
        assert all(leaf.is_deleted() for leaf in old_leaves)
        new_ptrs = {
            leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(eng.cache)
        }
        # in-place update: the new pools live in the donated buffers
        assert old_ptrs & new_ptrs, (old_ptrs, new_ptrs)

    def test_paged_decode_zero_added_jit_misses(self, lm_and_params):
        """The block-table arrays are fixed-shape batch inputs: after the
        first decode trace, subsequent steps (block lists growing, slots
        retiring) must not retrace."""
        lm, p = lm_and_params
        eng = make_engine(lm, p, True)
        for r in mixed_requests():
            eng.submit(r)
        for _ in range(3):
            eng.step()
        entries = eng._decode._cache_size()
        assert entries >= 1  # decode has been traced by now
        eng.run_until_done(max_steps=200)
        assert eng._decode._cache_size() == entries


# ---------------------------------------------------------------------------
# Truncation at KV capacity (overflow regression)
# ---------------------------------------------------------------------------


class TestKVCapacityTruncation:
    @pytest.mark.parametrize("paged", [False, True])
    def test_decode_past_max_seq_truncates_loudly(self, lm_and_params, paged):
        """Regression: a request decoding past max_seq used to clamp the
        dynamic_update_slice index and silently overwrite the last KV
        entry forever.  It must instead finish with ``truncated`` set and
        be counted in EngineStats."""
        lm, p = lm_and_params
        reqmod._next_id = 0
        eng = ServingEngine(
            lm, p,
            BatchingConfig(n_slots=2, max_seq=16, paged=paged, page_size=8),
        )
        r = Request(prompt=list(range(1, 9)), max_new_tokens=100)
        eng.submit(r)
        eng.run_until_done(max_steps=300)
        assert r.done and r.truncated
        # prompt 8 + g generated; the next feed position (8 + g - 1) must
        # stay < max_seq=16 -> exactly 9 tokens, none written past the end
        assert len(r.generated) == 9
        assert eng.stats.truncated_requests == 1

    def test_prompt_longer_than_max_seq_rejected(self, lm_and_params):
        lm, p = lm_and_params
        eng = make_engine(lm, p, False, max_seq=16)
        with pytest.raises(ValueError, match="max_seq"):
            eng.submit(Request(prompt=list(range(20)), max_new_tokens=1))

    def test_truncated_round_trips_through_request_state(self):
        r = Request(prompt=[1, 2], max_new_tokens=4)
        r.truncated = True
        d = r.to_state()
        assert Request.from_state(d).truncated is True
        d.pop("truncated")  # pre-paged snapshot blob
        assert Request.from_state(d).truncated is False


# ---------------------------------------------------------------------------
# Crash consistency
# ---------------------------------------------------------------------------


class TestPagedSnapshotRestore:
    def test_snapshot_restore_bit_identical(self, lm_and_params, tmp_path):
        """Snapshot a paged engine mid-run, restore into a fresh engine,
        and finish: tokens, block table, owner map, and free list must all
        match the uninterrupted run."""
        lm, p = lm_and_params

        ref_eng = make_engine(lm, p, True)
        for r in mixed_requests(new=8):
            ref_eng.submit(r)
        ref_eng.run_until_done(max_steps=200)
        ref_toks = {
            r.req_id: list(r.generated) for r in ref_eng.sched.finished
        }

        e1 = make_engine(lm, p, True)
        for r in mixed_requests(new=8):
            e1.submit(r)
        for _ in range(5):
            e1.step()
        e1.snapshot(str(tmp_path))
        table_at_snap = e1.paged.block_table.copy()

        reqmod._next_id = 0
        e2 = make_engine(lm, p, True)
        e2.restore(str(tmp_path))
        np.testing.assert_array_equal(e2.paged.block_table, table_at_snap)
        e2.run_until_done(max_steps=200)
        toks = {r.req_id: list(r.generated) for r in e2.sched.finished}
        assert toks == ref_toks
        # all blocks returned once everything drained
        assert e2.paged.n_free == e2.paged.n_pool - 1

    def test_layout_mismatch_rejected(self, lm_and_params, tmp_path):
        """A paged snapshot must not restore into a dense engine (and vice
        versa) — the cache leaves would silently reinterpret."""
        lm, p = lm_and_params
        e1 = make_engine(lm, p, True)
        for r in mixed_requests(n=2):
            e1.submit(r)
        e1.step()
        e1.snapshot(str(tmp_path))
        dense = make_engine(lm, p, False)
        with pytest.raises(ValueError):
            dense.restore(str(tmp_path))
