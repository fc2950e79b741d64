"""Qwen3-Next at tiny sizes on the CPU: Gated DeltaNet layers beside gated
attention, MoE with a gated shared expert in every layer, and one chip's
share of the experts, against the plain reference
``benchmark/reference/qwen3_next.py``.

* Prefill, then decode through the engine's cache, against the
  reference's full forward; the same run with the recurrent state kept in
  bfloat16 misses the tolerance.
* The chunked (WY) prefill recurrence against the token-by-token rule,
  across chunk boundaries.
* The ``gated_delta_decode`` kernel (interpret mode) against its oracle
  in ``kernels/ref.py``, on the layer-stacked state in place.
* The held shares of one layer add up to the whole layer, the gated
  shared expert counted once.
* Two requests served side by side: every served token is the argmax of
  logits that match the reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels import ops, ref as kref
from repro.models import LM
from repro.models import gated_delta as gdn
from repro.models.moe import init_moe, moe_block, moe_local, moe_reference, route
from repro.serving import BatchingConfig, Request, ServingEngine
from repro.telemetry import Telemetry

E, K = 16, 3


def _arch(held=0, held_offset=0, exec_mode="dense", n_layers=8):
    """Qwen3-Next at tiny widths: two periods of [3 linear + 1 gated
    attention] layers, 16 router outputs top-3, one gated shared expert."""
    arch = get_arch("qwen3-next-80b-a3b").reduced(n_layers=n_layers)
    return dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, n_experts=E, top_k=K, held=held, held_offset=held_offset,
        expert_exec=exec_mode, capacity_factor=E / K, min_capacity=64))


def _tree(lm, seed):
    """Seeded weights in the program's tree, from the benchmark's maker."""
    from benchmark import weights as wmod

    w, _ = wmod.make_weights(lm.abstract_params(), lm.arch.d_model, seed)
    return w


def _dims(arch):
    from benchmark.reference import qwen3_next as ref

    m, a, lin = arch.moe, arch.attn, arch.linear
    return ref.Dims(
        d=arch.d_model, vocab=arch.vocab_size, n_layers=arch.n_layers,
        n_dense_lead=0, d_ff_dense=arch.d_ff, attn="gqa", n_heads=a.n_heads,
        n_kv_heads=a.n_kv_heads, head_dim=a.d_head, q_lora=0, kv_lora=0, qk_nope=0,
        qk_rope=0, v_head=0, n_experts=m.n_experts, top_k=m.top_k, d_expert=m.d_expert,
        n_shared=m.n_shared, norm_topk=m.norm_topk_prob, routed_scale=1.0,
        rope_theta=a.rope_theta, eps=1e-6, n_held=m.n_held, held_offset=m.held_offset,
        lin_k_heads=lin.n_k_heads, lin_v_heads=lin.n_v_heads, lin_k_dim=lin.d_k,
        lin_v_dim=lin.d_v, conv_width=lin.conv_width,
        full_every=lin.full_attention_interval, rotary_dim=a.rotary_dim)


def _reference_logits(w, dm, seq, mode):
    """The reference's full forward over one sequence: logits at every
    position."""
    from benchmark.reference import qwen3_next as ref
    from benchmark.reference.moe_transformer import Ops, rmsnorm

    rows, _ = ref.pack_rows([seq], len(seq))
    with jax.default_matmul_precision("highest"):
        h = ref.hidden_states(w, dm, rows, mode)[0]
        return np.asarray(Ops(mode).mm(rmsnorm(h, w["final_norm"]["scale"], dm.eps),
                                       w["w_out"]))


def _serve(lm, w, prompts, max_new, n_slots=2, state_dtype=None):
    """Serve ``prompts`` side by side; returns (requests, each request's
    logits at its served positions).  ``state_dtype`` re-types the cache's
    recurrent state."""
    eng = ServingEngine(lm, w, BatchingConfig(n_slots=n_slots, max_seq=64))
    if state_dtype is not None:
        eng.cache["recurrent"] = eng.cache["recurrent"].astype(state_dtype)
    seen = {}
    prefill, decode = eng._prefill_chunk, eng._decode

    def tap_prefill(params, batch, cache, slot):
        out = prefill(params, batch, cache, slot)
        seen.setdefault(int(slot), []).append(np.asarray(out[0][1])[0, -1])
        return out

    def tap_decode(params, batch, cache):
        out = decode(params, batch, cache)
        logits = np.asarray(out[0][1])[:, 0]
        for r in eng.sched.decode_batch():
            seen[r.slot].append(logits[r.slot])
        return out

    eng._prefill_chunk, eng._decode = tap_prefill, tap_decode
    reqs = [Request(prompt=list(p), max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    return reqs, [np.stack(seen[r.slot]) for r in reqs], eng


def test_engine_prefill_then_decode_matches_reference():
    """Float32 weights served through the engine (prefill, then decode
    through the recurrent, conv and KV caches) give the reference's logits
    at every served position.  Tolerance: 1e-4 of the logits' spread, for
    float32 arithmetic in another order (the chunked WY recurrence against
    the token-by-token rule, chunked attention, capacity dispatch) through
    8 layers and the routers' softmax.  The reference in bfloat16 misses
    it by more than 10x, and so does the program with its recurrent state
    held in bfloat16."""
    arch = _arch(held=8, held_offset=4, exec_mode="dual_path_cost")
    lm = LM(arch, dtype=jnp.float32)
    w = _tree(lm, 2**32 + 11)
    prompt = list(np.random.default_rng(5).integers(1, arch.vocab_size, 11))
    (req,), (got,), eng = _serve(lm, w, [prompt], 8, n_slots=1)
    assert len(got) == 8
    dm = _dims(arch)
    seq = prompt + req.generated[:-1]
    ref32 = _reference_logits(w, dm, seq, "f32")[len(prompt) - 1:]
    ref16 = _reference_logits(w, dm, seq, "bf16")[len(prompt) - 1:]
    tol = 1e-4 * float(ref32.std())
    err = np.abs(got - ref32).max()
    assert err < tol, (err, tol)
    assert np.abs(ref16 - ref32).max() > 10 * tol
    assert eng.stats.routed_tokens > 0
    # the same run with the recurrent state rounded to bfloat16 each step
    (req16,), (got16,), _ = _serve(lm, w, [prompt], 8, n_slots=1,
                                   state_dtype=jnp.bfloat16)
    n = min(len(got16), len(got))
    assert np.abs(got16[:n] - ref32[:n]).max() > 10 * tol


def test_engine_serves_the_argmax_of_reference_logits():
    """Two requests of different lengths decode in the same steps, each
    from its own slot's recurrent, conv and KV state: each served token is
    the argmax of the logits the engine computed for it, and those logits
    are the reference's within the tolerance above."""
    arch = _arch(held=8, held_offset=0, exec_mode="dual_path_cost")
    lm = LM(arch, dtype=jnp.float32)
    w = _tree(lm, 7)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, arch.vocab_size, 13), rng.integers(1, arch.vocab_size, 6)]
    reqs, logits, _ = _serve(lm, w, prompts, 6)
    dm = _dims(arch)
    for p, r, got in zip(prompts, reqs, logits):
        assert len(r.generated) == len(got) == 6
        np.testing.assert_array_equal(np.argmax(got[:, :arch.vocab_size], -1),
                                      r.generated)
        want = _reference_logits(w, dm, list(p) + r.generated[:-1], "f32")[len(p) - 1:]
        assert np.abs(got - want).max() < 1e-4 * float(want.std())


def _rule_inputs(B, S, H, dk, dv, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (B, S, H, dk))),
            jax.random.normal(ks[2], (B, S, H, dv)),
            -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H))))


@pytest.mark.parametrize("S,chunk", [(20, 8), (64, 16), (7, 64)])
def test_chunked_prefill_matches_token_recurrence(S, chunk):
    """The WY chunks against the one-token oracle stepped over the
    sequence, across chunk boundaries and a padded last chunk.  Tolerance
    1e-5: float32 sums in another order over at most 64 tokens of unit
    keys."""
    B, H, dk, dv = 2, 3, 8, 16
    q, k, v, g, beta = _rule_inputs(B, S, H, dk, dv, S)
    o, state = gdn.chunked_gated_delta(q, k, v, g, beta, chunk=chunk)
    st = jnp.zeros((1, B, H, dk, dv), jnp.float32)
    want = []
    for t in range(S):
        ot, st = kref.gated_delta_decode_ref(st, jnp.int32(0), q[:, t], k[:, t], v[:, t],
                                             jnp.exp(g[:, t]), beta[:, t])
        want.append(ot)
    np.testing.assert_allclose(np.asarray(o), np.stack(want, 1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(st[0]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("L,B,H,dk,dv,hb,layer", [
    (3, 4, 8, 16, 16, 4, 1), (2, 2, 4, 32, 16, 4, 0), (2, 3, 32, 16, 16, 16, 1)])
def test_gated_delta_kernel_matches_oracle(L, B, H, dk, dv, hb, layer):
    """Interpret mode: the one layer advanced as the oracle advances it
    (1e-5: float32 sums in another order), every other layer's state left
    bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(L * 100 + H), 7)
    state = jax.random.normal(ks[0], (L, B, H, dk, dv), jnp.float32)
    q = jax.random.normal(ks[1], (B, H, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (B, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (B, H, dv))
    decay = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    o_ref, s_ref = kref.gated_delta_decode_ref(state, jnp.int32(layer), q, k, v, decay, beta)
    o, s = ops.gated_delta_decode(state, jnp.int32(layer), q, k, v, decay, beta, hb=hb,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5, atol=1e-5)
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(np.asarray(s)[others], np.asarray(state)[others])


@pytest.mark.parametrize("exec_mode", ["dense", "dual_path_cost"])
def test_shares_add_up_to_the_whole_layer(exec_mode):
    """Eight shares of two experts each: their routed outputs, plus the
    sigmoid-gated shared expert once, are the uncut layer's (``moe_block``
    of the whole layer); their counts partition the router's."""
    whole = _arch(exec_mode=exec_mode)
    p = init_moe(jax.random.PRNGKey(3), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, whole.d_model), jnp.float32)
    sp = p["shared"]
    shared = ((jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
              * jax.nn.sigmoid(x @ sp["w_expert_gate"]))
    want = moe_reference(p, x, whole) + shared
    np.testing.assert_allclose(
        np.asarray(moe_block(p, x[None], whole).y[0]), np.asarray(want),
        rtol=1e-5, atol=1e-5)
    total, counts = jnp.zeros_like(x), 0
    n = E // 8
    for s in range(8):
        arch = _arch(held=n, held_offset=s * n, exec_mode=exec_mode)
        ps = {k: (v[s * n:(s + 1) * n] if k in ("w_gate", "w_up", "w_down") else v)
              for k, v in p.items() if k != "shared"}
        out = moe_local(ps, x, arch)
        assert int(out.n_dropped) == 0
        total, counts = total + out.y, counts + np.asarray(out.counts)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(counts, np.asarray(route(x, p["w_router"], whole.moe).counts))


def test_cache_holds_both_kinds_of_state():
    """The per-slot cache: the full layers' head-major KV, the linear
    layers' float32 recurrent state and conv tails, slot axis second; the
    engine gauges the fixed-size state's bytes; the paged KV pool refuses
    the family."""
    arch = _arch()
    lm = LM(arch, dtype=jnp.bfloat16)
    lin, a = arch.linear, arch.attn
    c = jax.eval_shape(lambda: lm.init_cache(3, 32))
    assert c["kv"][0].shape == (2, 3, a.n_kv_heads, 32, a.d_head)
    assert c["recurrent"].shape == (6, 3, lin.n_v_heads, lin.d_k, lin.d_v)
    assert c["recurrent"].dtype == jnp.float32
    assert c["conv"].shape == (6, 3, lin.conv_width - 1, lin.conv_dim)
    tel = Telemetry(capacity=1 << 10, enabled=True)
    eng = ServingEngine(lm, lm.init(jax.random.PRNGKey(0)),
                        BatchingConfig(n_slots=3, max_seq=32), telemetry=tel)
    want = 6 * 3 * lin.n_v_heads * lin.d_k * lin.d_v * 4 + 6 * 3 * 3 * lin.conv_dim * 2
    assert tel.gauges()["engine/recurrent_state_bytes"] == want
    with pytest.raises(ValueError, match="paged KV cache requires a gqa"):
        lm.init_paged_cache(8, 16)


@pytest.fixture
def _pallas(monkeypatch):
    monkeypatch.setenv("REPRO_DUAL_BACKEND", "pallas")


def test_held_share_reads_the_stacks_in_place(_pallas):
    """On the Pallas dual path the expert kernels of every layer take the
    whole (L*held, ...) stacks through the period scan, no (held, d, f)
    value is made, and the decode step matches the dense oracle."""
    from tests.test_expert_stack_in_place import _eqns, _kernel_weight_rows, _shapes

    # 6 held: unlike 4, no other stack of a period has a (held, d, f) slice
    held, off = 6, 8
    arch = _arch(held=held, held_offset=off, exec_mode="dual_path")
    lm = LM(arch, dtype=jnp.float32)
    assert lm.moe_layers_in_place() == arch.n_layers
    p = _tree(lm, 2**31 + 3)
    B = 3
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, 1), 1, 255),
             "position": jnp.arange(B, dtype=jnp.int32) + 3}
    cache = jax.tree.map(lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape, a.dtype),
                         lm.init_cache(B, 16))
    jaxpr = jax.make_jaxpr(lm.decode_step)(p, batch, cache).jaxpr
    assert set(_kernel_weight_rows(jaxpr, arch)) == {arch.n_layers * held}
    d, f = arch.d_model, arch.moe.d_expert
    assert not {(held, d, f), (held, f, d)} & _shapes(jaxpr)
    assert any(e.primitive.name == "pallas_call" for e in _eqns(jaxpr))
    logits, _, aux = jax.jit(lm.decode_step)(p, batch, cache)
    dense = LM(_arch(held=held, held_offset=off), dtype=jnp.float32)
    ref_logits, _, ref_aux = jax.jit(dense.decode_step)(p, batch, cache)
    counts = np.asarray(aux.counts)
    assert counts.shape == (arch.n_layers, E)
    assert not counts[:, :off].any() and not counts[:, off + held:].any()
    np.testing.assert_array_equal(counts, np.asarray(ref_aux.counts))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-5, atol=1e-5)


def test_named_scopes_in_both_programs():
    """The linear layers' conv, recurrence and output, and the gated
    attention, carry the named scopes a trace reads, in prefill and
    decode."""
    arch = _arch(n_layers=4)
    lm = LM(arch, dtype=jnp.float32)
    p = lm.abstract_params()
    pre = jax.jit(lm.prefill).lower(p, {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)})
    dec = jax.jit(lm.decode_step).lower(
        p, {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32),
            "position": jax.ShapeDtypeStruct((2,), jnp.int32)},
        jax.eval_shape(lambda: lm.init_cache(2, 16)))
    for lowered in (pre, dec):
        text = lowered.as_text(debug_info=True)
        for scope in ("gated_delta/conv", "gated_delta/recurrence", "gated_delta/out",
                      "attention"):
            assert scope in text, scope
