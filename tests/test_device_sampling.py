"""The greedy pick inside the compiled steps: a greedy engine serves the
argmax of each step's own logits while copying only ``(B,)`` int32 ids to
the host; an engine that samples on the host still reads the logits and
draws with its RNG.  The compiled programs keep the names the benchmark's
trace reduction finds them by (``benchmark/readers.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import LM
from repro.serving import BatchingConfig, Request, ServingEngine
from repro.serving.engine import decode_program, greedy_ids
from repro.telemetry import Telemetry


def _qwen3(vocab=None):
    arch = get_arch("qwen3-moe-30b-a3b").reduced()
    if vocab is not None:  # below the 128-multiple the logits are padded to
        arch = dataclasses.replace(arch, vocab_size=vocab)
    lm = LM(arch, dtype=jnp.bfloat16)
    return lm, lm.init(jax.random.PRNGKey(0))


def _deepseek_share():
    from tests.test_deepseek_share import _arch, _tree

    lm = LM(_arch(held=4, held_offset=4, exec_mode="dual_path_cost"),
            dtype=jnp.bfloat16)
    return lm, _tree(lm, 2**32 + 7)


CASES = {
    "qwen3-dense-kv": (_qwen3, False),
    "qwen3-paged-kv": (_qwen3, True),
    "qwen3-padded-vocab": (lambda: _qwen3(vocab=250), False),
    "deepseek-share-dense-kv": (_deepseek_share, False),  # paged KV is GQA only
}


def _engine(make, paged, n_slots=4, **kw):
    lm, params = make()
    cfg = BatchingConfig(n_slots=n_slots, max_seq=64, paged=paged, page_size=8)
    return ServingEngine(lm, params, cfg, **kw)


def _requests(vocab, n=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(0, vocab, size=plen)),
                    max_new_tokens=new)
            for plen, new in zip((5, 9, 12, 7, 16), (6, 3, 8, 5, 4))][:n]


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_tokens_are_the_argmax_of_the_steps_logits(case):
    """Step by step, each token the engine serves is the host argmax of
    the logits the same compiled call returned (over the true vocabulary,
    first index on ties), for the prefill's token and every decode row."""
    make, paged = CASES[case]
    eng = _engine(make, paged)
    vocab = eng.lm.arch.vocab_size
    prefill, decode = eng._prefill_chunk, eng._decode
    picks = {}

    def host_argmax(out):
        ids, logits = out[0]
        logits = np.asarray(logits)[:, -1].astype(np.float32)
        assert logits.shape[-1] == eng.lm.vocab_padded
        am = logits[:, :vocab].argmax(-1)
        # the program's own ids are that argmax too, int32 and (B,)
        assert ids.dtype == jnp.int32 and ids.shape == am.shape
        np.testing.assert_array_equal(np.asarray(ids), am)
        return am

    def tap_prefill(*a):
        out = prefill(*a)
        picks.setdefault("prefill", {})[int(a[3])] = int(host_argmax(out)[0])
        return out

    def tap_decode(*a):
        out = decode(*a)
        picks["decode"] = host_argmax(out)
        return out

    eng._prefill_chunk, eng._decode = tap_prefill, tap_decode
    reqs = _requests(vocab)
    for r in reqs:
        eng.submit(r)
    n_decode = 0
    while not eng.sched.idle:
        before = {id(r): len(r.generated) for r in reqs}
        picks.clear()
        eng.step()
        n_decode += "decode" in picks
        for r in reqs:
            new, expected = r.generated[before[id(r)]:], []
            if before[id(r)] == 0 and new:
                expected.append(picks["prefill"][r.slot])
            if len(new) > len(expected):
                expected.append(int(picks["decode"][r.slot]))
            assert new == expected, (case, r.req_id)
    assert n_decode > 0
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)
    assert decode._cache_size() == 1


def test_greedy_ids_take_the_first_index_on_ties_and_skip_padding():
    """Ties go to the lower index, as numpy's argmax; columns past the
    true vocabulary are never picked, whatever they hold."""
    logits = jnp.asarray([[[1.0, 3.0, 3.0, 9.0]], [[2.0, 2.0, 0.0, 9.0]]],
                         jnp.bfloat16)
    ids = greedy_ids(logits, vocab_size=3)
    assert ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ids), [1, 0])


def _step_rows(tel, name):
    """Per-step increments of a counter, in step order."""
    vals = [e["value"] for e in tel.events()
            if e["kind"] == "point" and e["name"] == name]
    return np.diff([0.0] + vals)


@pytest.mark.parametrize("greedy", [True, False])
def test_device_sampled_rows_counts_live_rows_when_greedy(greedy):
    """``engine/device_sampled_rows`` counts each decode step's live rows
    when the compiled step picks their tokens, and stays 0 where the engine
    samples on the host (which still serves every token, drawn with its
    RNG from the copied logits)."""
    tel = Telemetry(enabled=True)
    eng = _engine(_qwen3, False, greedy=greedy, telemetry=tel, seed=3)
    rng_state = eng.rng.bit_generator.state
    reqs = _requests(eng.lm.arch.vocab_size, n=3)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)
    assert all(0 <= t < eng.lm.arch.vocab_size for r in reqs for t in r.generated)
    live = [e["value"] for e in tel.events()
            if e["kind"] == "span" and e["name"] == "engine/decode"]
    rows = _step_rows(tel, "engine/device_sampled_rows")
    assert len(rows) == len(live) > 0
    if greedy:
        np.testing.assert_array_equal(rows, live)
        assert tel.counters()["engine/device_sampled_rows"] == eng.stats.decode_tokens
        assert eng.rng.bit_generator.state == rng_state
    else:
        assert not rows.any()
        assert tel.counters()["engine/device_sampled_rows"] == 0.0
        assert eng.rng.bit_generator.state != rng_state


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def test_compiled_programs_keep_their_names():
    """The benchmark finds the decode and prefill programs in a device
    trace by ``decode_step`` / ``prefill_chunk`` in their module names."""
    eng = _engine(_qwen3, False)
    seen = {}
    prefill, decode = eng._prefill_chunk, eng._decode

    def tap(name, fn):
        def call(*a):
            seen.setdefault(name, _abstract(a))
            return fn(*a)
        return call

    eng._prefill_chunk = tap("prefill", prefill)
    eng._decode = tap("decode", decode)
    eng.submit(_requests(eng.lm.arch.vocab_size, n=1)[0])
    eng.run_until_done()
    prefill_text = prefill.lower(*seen["prefill"]).as_text()
    decode_text = decode.lower(*seen["decode"]).as_text()
    assert "prefill_chunk" in prefill_text.split("\n", 1)[0]
    assert decode_text.startswith("module @jit_decode_step")


def test_decode_program_returns_ids_beside_the_models_logits():
    """``decode_program`` wraps ``lm.decode_step`` unchanged: the same
    logits, cache and aux, with the ``(B,)`` int32 greedy ids added."""
    lm, params = _qwen3()
    B = 3
    cache = lm.init_cache(B, 16)
    batch = {"tokens": jnp.asarray([[5], [17], [200]], jnp.int32),
             "position": jnp.asarray([0, 2, 4], jnp.int32)}
    (ids, logits), _, aux = jax.jit(decode_program(lm))(params, batch, cache)
    ref_logits, _, ref_aux = jax.jit(lm.decode_step)(params, batch, cache)
    assert ids.shape == (B,) and ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    np.testing.assert_array_equal(np.asarray(aux.counts), np.asarray(ref_aux.counts))
    np.testing.assert_array_equal(
        np.asarray(ids),
        np.asarray(ref_logits)[:, 0, : lm.arch.vocab_size].astype(np.float32).argmax(-1),
    )
