"""Serving engine: continuous batching invariants + Sieve runtime loop."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import LM
from repro.serving import BatchingConfig, Request, ServingEngine
from repro.serving.batching import SlotScheduler


def make_engine(arch_name="qwen3-moe-30b-a3b", n_slots=4, policy="sieve", **bk):
    arch = get_arch(arch_name).reduced()
    lm = LM(arch, dtype=jnp.float32)
    p = lm.init(jax.random.PRNGKey(0))
    return ServingEngine(
        lm, p, BatchingConfig(n_slots=n_slots, max_seq=64, **bk), policy=policy
    )


def reqs(n, plen=8, new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=list(rng.integers(0, 250, size=plen)), max_new_tokens=new)
        for _ in range(n)
    ]


class TestSlotScheduler:
    def test_admission_respects_slot_count(self):
        s = SlotScheduler(BatchingConfig(n_slots=2, max_seq=32))
        for r in reqs(5):
            s.submit(r)
        admitted = s.admit()
        assert len(admitted) == 2
        assert len(s.queue) == 3

    def test_retire_frees_slots(self):
        s = SlotScheduler(BatchingConfig(n_slots=2, max_seq=32))
        for r in reqs(3, new=0):
            s.submit(r)
        s.admit()
        for r in s.active:
            r.prefill_done = len(r.prompt)  # max_new=0 -> instantly done
        done = s.retire(0.0)
        assert len(done) == 2
        assert len(s.admit()) == 1


class TestEngineStats:
    def test_drop_rate_zero_before_any_routed_token(self):
        """An engine that never routed a token must report 0.0, not divide
        by zero (regression: drop_rate on a fresh/dense-model engine)."""
        from repro.serving.engine import EngineStats

        assert EngineStats().drop_rate == 0.0
        assert EngineStats(dropped_tokens=3).drop_rate == 0.0
        s = EngineStats(dropped_tokens=1, routed_tokens=4)
        assert s.drop_rate == 0.25


class TestEngine:
    def test_all_requests_complete(self):
        eng = make_engine()
        for r in reqs(6):
            eng.submit(r)
        done = eng.run_until_done()
        assert len(done) == 6
        for r in done:
            assert len(r.generated) == r.max_new_tokens

    def test_greedy_deterministic(self):
        outs = []
        for _ in range(2):
            eng = make_engine()
            for r in reqs(3, seed=1):
                eng.submit(r)
            done = eng.run_until_done()
            outs.append([tuple(r.generated) for r in sorted(done, key=lambda q: q.req_id)])
        # same prompts + greedy -> same generations modulo batching order
        assert sorted(outs[0]) == sorted(outs[1])

    def test_engine_output_matches_standalone_decode(self):
        """A single request through the engine equals prefill+decode done
        by hand (continuous batching must not change results)."""
        arch = get_arch("granite-3-2b").reduced()
        lm = LM(arch, dtype=jnp.float32)
        p = lm.init(jax.random.PRNGKey(0))
        prompt = list(np.random.default_rng(0).integers(0, 250, size=8))
        eng = ServingEngine(lm, p, BatchingConfig(n_slots=2, max_seq=64))
        eng.submit(Request(prompt=prompt, max_new_tokens=5))
        done = eng.run_until_done()
        got = done[0].generated

        logits, cache_pf, _ = jax.jit(lm.prefill)(p, {"tokens": jnp.asarray([prompt])})
        cache = lm.init_cache(2, 64)  # engine slots/max_seq
        cache = jax.tree.map(
            lambda big, small: big.at[:, :1, :, : small.shape[3]].set(
                small.astype(big.dtype)
            ),
            cache,
            cache_pf,
        )
        exp = [int(jnp.argmax(logits[0, -1]))]
        pos = len(prompt)
        step = jax.jit(lm.decode_step)
        for _ in range(4):
            db = {
                "tokens": jnp.asarray([[exp[-1]], [0]], jnp.int32),
                "position": jnp.asarray([pos, 0], jnp.int32),
            }
            lg, cache, _ = step(p, db, cache)
            exp.append(int(jnp.argmax(lg[0, 0, : arch.vocab_size])))
            pos += 1
        assert got == exp

    def test_sieve_loop_records_partitions_and_table(self):
        eng = make_engine(policy="sieve")
        for r in reqs(4):
            eng.submit(r)
        eng.run_until_done()
        assert len(eng.stats.partitions) > 0
        assert eng.cost_table.coverage >= 1
        for rec in eng.stats.partitions:
            assert rec["n_gpu"] + rec["n_pim"] >= 0
            assert rec["t_total_est"] >= 0

    def test_colocated_pd_bounded_prefills(self):
        eng = make_engine(n_slots=4, colocated_pd=True, max_prefills_per_step=1)
        for r in reqs(4):
            eng.submit(r)
        eng.step()
        # only 1 prefill allowed in the first step
        prefilled = [r for r in eng.sched.active if r.prefill_done > 0]
        assert len(prefilled) == 1

    def test_buffer_donation_decode_reuses_kv_cache(self):
        """The decode step donates the KV cache (argnum 2): the stale
        cache buffers must be freed and the new cache must reuse the
        donated memory in place — no full-cache copy per decode step."""
        eng = make_engine()
        for r in reqs(4):
            eng.submit(r)
        eng.step()  # prefill + first decode
        old_leaves = jax.tree.leaves(eng.cache)
        old_ptrs = {leaf.unsafe_buffer_pointer() for leaf in old_leaves}
        eng.step()  # pure decode
        assert all(leaf.is_deleted() for leaf in old_leaves)
        new_ptrs = {
            leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(eng.cache)
        }
        # in-place update: the new cache lives in the donated buffers
        assert old_ptrs & new_ptrs, (old_ptrs, new_ptrs)

    def test_buffer_donation_prefill_frees_stale_cache(self):
        eng = make_engine()
        old_leaves = jax.tree.leaves(eng.cache)
        eng.submit(reqs(1)[0])
        eng.step()  # prefill donates the cache it consumed
        assert all(leaf.is_deleted() for leaf in old_leaves)

    def test_donation_preserves_generations(self):
        """Donation must not change results: interleaved prefills and
        decodes over donated caches reproduce the no-donation outputs
        (cross-checked against standalone decode in
        test_engine_output_matches_standalone_decode)."""
        outs = []
        for _ in range(2):
            eng = make_engine()
            for r in reqs(5, seed=3):
                eng.submit(r)
            done = eng.run_until_done()
            outs.append(
                [tuple(r.generated) for r in sorted(done, key=lambda q: q.req_id)]
            )
        assert outs[0] == outs[1]

    def test_sieve_refresh_donates_stale_state(self):
        """_refresh_sieve_state frees the previous SieveState's device
        buffers (the engine can never read them again)."""
        eng = make_engine()  # qwen3 arch ships dual_path_cost
        assert eng.uses_cost_split
        stale = eng._sieve_state
        eng.cost_table.update(3, 1e-4)  # bump the table version
        eng._refresh_sieve_state(step=1)
        assert eng._sieve_state is not stale
        assert all(
            leaf.is_deleted() for leaf in jax.tree.leaves(stale)
        )

    def test_prefill_compiles_once_per_prompt_length(self):
        """The slot is a traced argument: prompts of one length admitted
        into different slots share one prefill executable."""
        eng = make_engine(n_slots=2)
        for r in reqs(2):
            eng.submit(r)
        eng.step()
        assert sorted(r.slot for r in eng.sched.active) == [0, 1]
        assert eng._prefill_chunk._cache_size() == 1

    def test_throughput_accounting(self):
        eng = make_engine()
        for r in reqs(2, new=3):
            eng.submit(r)
        eng.run_until_done()
        # first token comes from prefill; 2 more from decode per request
        assert eng.stats.decode_tokens == 2 * 2
        assert eng.stats.prefill_tokens == 2 * 8
