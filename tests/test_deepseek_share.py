"""DeepSeek-V2's published routing and rope, and one chip's share of an
expert layer, at tiny sizes on the CPU.

* Group-limited routing against a numpy transcription of DeepSeek-V2's
  ``MoEGate``; Qwen3's plain routing unchanged, bit for bit.
* YaRN inverse frequencies, cos/sin scale and MLA softmax scale against a
  numpy transcription of ``DeepseekV2YarnRotaryEmbedding`` /
  ``yarn_get_mscale``.
* A held share of the experts: the shares' routed outputs, with the shared
  experts counted once, add up to the uncut layer; prefill then decode
  through the serving engine agrees with the plain reference
  ``benchmark/reference/deepseek_v2.py``; the share takes the in-place
  expert path; the engine's scheduler runs over the held experts.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import YarnConfig, get_arch
from repro.models import LM
from repro.models.attention import _mla_softmax_scale
from repro.models.layers import apply_rope, rope_freqs, rope_mscale
from repro.models.moe import init_moe, moe_local, moe_reference, route
from repro.serving import BatchingConfig, Request, ServingEngine
from repro.telemetry import Telemetry

E, G, TOPK_GROUP, K, SCALE = 16, 4, 2, 3, 16.0
PUBLISHED_YARN = YarnConfig()  # factor 40, 4096, beta 32/1, mscale 0.707/0.707


def _arch(held=0, held_offset=0, n_layers=3, exec_mode="dense", d_model=64):
    """DeepSeek-V2 at tiny widths: 1 dense + 2 MoE layers, 16 router
    outputs in 4 groups, top-3 from the best 2 groups, scores x 16."""
    arch = get_arch("deepseek-v2-236b").reduced(n_layers=n_layers, d_model=d_model)
    return dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, n_experts=E, top_k=K, n_group=G, topk_group=TOPK_GROUP,
        held=held, held_offset=held_offset, expert_exec=exec_mode,
        capacity_factor=E / K, min_capacity=64))


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def _softmax(z):
    z = z - z.max(-1, keepdims=True)
    return np.exp(z) / np.exp(z).sum(-1, keepdims=True)


def moegate_numpy(logits, n_group, topk_group, top_k, norm_topk_prob, scale):
    """DeepSeek-V2's MoEGate.forward (topk_method group_limited_greedy),
    transcribed: (topk_idx, topk_weight) per token."""
    n = logits.shape[0]
    scores = _softmax(logits.astype(np.float64))
    group_scores = scores.reshape(n, n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=-1)[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1, axis=1)
    score_mask = np.repeat(group_mask, scores.shape[1] // n_group, axis=1)
    tmp_scores = np.where(score_mask.astype(bool), scores, 0.0)
    topk_idx = np.argsort(-tmp_scores, axis=-1)[:, :top_k]
    topk_weight = np.take_along_axis(tmp_scores, topk_idx, axis=1)
    if top_k > 1 and norm_topk_prob:
        topk_weight = topk_weight / (topk_weight.sum(-1, keepdims=True) + 1e-20)
    else:
        topk_weight = topk_weight * scale
    return topk_idx, topk_weight


def _distinct_logits(T, seed):
    """Each token's logits are a permutation of a grid 0.2 apart: no two
    scores, and so no two group maxima, tie."""
    rng = np.random.default_rng(seed)
    grid = np.linspace(0.0, 3.0, E, dtype=np.float32)
    return np.stack([rng.permutation(grid) for _ in range(T)])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_routing_matches_moegate(seed):
    cfg = _arch().moe
    logits = _distinct_logits(64, seed)
    # x @ identity: the router's logits are exactly these
    r = route(jnp.asarray(logits), jnp.eye(E, dtype=jnp.float32), cfg)
    want_i, want_w = moegate_numpy(logits, G, TOPK_GROUP, K, False, SCALE)
    got_i, got_w = np.asarray(r.expert_idx), np.asarray(r.weights, np.float64)
    order_g, order_w = np.argsort(got_i, 1), np.argsort(want_i, 1)
    np.testing.assert_array_equal(np.take_along_axis(got_i, order_g, 1),
                                  np.take_along_axis(want_i, order_w, 1))
    np.testing.assert_allclose(np.take_along_axis(got_w, order_g, 1),
                               np.take_along_axis(want_w, order_w, 1), rtol=1e-6)
    # the group limit binds: some token's plain top-3 reaches a third group
    plain = np.argsort(-logits, 1)[:, :K] // (E // G)
    assert any(len(set(row)) > TOPK_GROUP for row in plain)
    assert all(len(set(row)) <= TOPK_GROUP for row in got_i // (E // G))
    np.testing.assert_array_equal(np.asarray(r.counts), np.bincount(want_i.ravel(), minlength=E))


def test_qwen3_routing_is_unchanged():
    """Qwen3 (one group, renormalised, scale 1) routes bit for bit as the
    formula before group routing was added."""
    cfg = get_arch("qwen3-moe-30b-a3b").moe
    assert (cfg.n_group, cfg.topk_group, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.n_held) == (1, 1, True, 1.0, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
    r = jax.jit(route, static_argnums=2)(x, w, cfg)

    @jax.jit
    def before(x, w_router):
        logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
        weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        frac = jnp.zeros((128,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (48 * cfg.top_k)
        aux = 128 * jnp.sum(probs.mean(0) * frac)
        counts = jnp.zeros((128,), jnp.int32).at[top_i.reshape(-1)].add(1)
        return top_i.astype(jnp.int32), weights.astype(x.dtype), aux, counts

    for got, want in zip(r, before(x, w)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kw,match", [
    ({"n_experts": 10}, "equal routing groups"),
    ({"topk_group": 5}, "topk_group"),
    ({"top_k": 9}, "exceeds"),
    ({"held": 8, "held_offset": 10}, "outside"),
])
def test_moe_config_refuses_inconsistent_routing(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(_arch().moe, **kw)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def yarn_numpy(dim, base, scaling_factor, original_max_position_embeddings,
               beta_fast, beta_slow, mscale, mscale_all_dim):
    """DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache's inv_freq and
    _mscale, and DeepseekV2Attention's softmax-scale factor, transcribed."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original_max_position_embeddings
                               / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    freq_inter = 1.0 / (scaling_factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    _mscale = get_mscale(scaling_factor, mscale) / get_mscale(scaling_factor, mscale_all_dim)
    softmax_factor = get_mscale(scaling_factor, mscale_all_dim) ** 2 if mscale_all_dim else 1.0
    return inv_freq, _mscale, softmax_factor


@pytest.mark.parametrize("scaling", [
    PUBLISHED_YARN,
    YarnConfig(factor=8.0, original_max_position=2048, beta_fast=16.0, beta_slow=2.0,
               mscale=1.0, mscale_all_dim=0.5),
], ids=["published", "unequal-mscale"])
def test_yarn_matches_deepseek(scaling):
    dim, base = 64, 1e4
    inv, m, sm = yarn_numpy(dim, base, scaling.factor, scaling.original_max_position,
                            scaling.beta_fast, scaling.beta_slow, scaling.mscale,
                            scaling.mscale_all_dim)
    got = np.asarray(rope_freqs(dim, base, scaling), np.float64)
    np.testing.assert_allclose(got, inv, rtol=2e-6)
    # extrapolated below the ramp, interpolated above it
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(inv[-1], rel=1e-6)
    assert rope_mscale(scaling) == pytest.approx(m, rel=1e-12)
    # rope itself, in the rotate-half layout: q*cos + rotate_half(q)*sin
    # with emb = cat(freqs, freqs) and the cache scaled by _mscale
    x = np.random.default_rng(0).standard_normal((2, 7, 3, dim)).astype(np.float32)
    pos = np.broadcast_to(np.arange(7) * 300, (2, 7))
    ang = pos[..., None] * inv
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)[..., None, :] * m
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)[..., None, :] * m
    rot = np.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    np.testing.assert_allclose(np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos), base,
                                                     scaling)),
                               x * cos + rot * sin, rtol=2e-4, atol=2e-4)
    attn = dataclasses.replace(get_arch("deepseek-v2-236b").attn, rope_scaling=scaling)
    assert _mla_softmax_scale(attn) == pytest.approx(sm / math.sqrt(128 + 64), rel=1e-12)


def test_published_softmax_scale():
    """mscale**2 = (0.1 * 0.707 * ln 40 + 1)**2, about 1.590."""
    attn = get_arch("deepseek-v2-236b").attn
    assert _mla_softmax_scale(attn) * math.sqrt(192) == pytest.approx(1.5895, abs=1e-3)
    assert rope_mscale(attn.rope_scaling) == 1.0


# ---------------------------------------------------------------------------
# The held share
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exec_mode", ["dense", "dual_path_cost"])
def test_shares_add_up_to_the_whole_layer(exec_mode):
    """Eight shares of two experts each: their routed outputs, plus the
    shared experts once, are the uncut layer's; their counts partition the
    router's."""
    whole = _arch(exec_mode=exec_mode)
    p = init_moe(jax.random.PRNGKey(3), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, whole.d_model), jnp.float32)
    sp = p["shared"]
    shared = (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    want = moe_reference(p, x, whole) + shared
    total, counts = jnp.zeros_like(x), 0
    n = E // 8
    for s in range(8):
        arch = _arch(held=n, held_offset=s * n, exec_mode=exec_mode)
        ps = {k: (v[s * n:(s + 1) * n] if k in ("w_gate", "w_up", "w_down") else v)
              for k, v in p.items() if k != "shared"}
        out = moe_local(ps, x, arch)
        c = np.asarray(out.counts)
        assert not c[: s * n].any() and not c[(s + 1) * n:].any()
        assert int(out.n_dropped) == 0
        total, counts = total + out.y, counts + c
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(counts, np.asarray(route(x, p["w_router"], whole.moe).counts))


def _tree(lm, seed):
    """Seeded float32 weights in the program's tree, from the benchmark's
    maker (routers of logit std ~1.3, so routing is far from uniform)."""
    from benchmark import weights as wmod

    w, _ = wmod.make_weights(lm.abstract_params(), lm.arch.d_model, seed)
    return w


def _dims(arch):
    from benchmark.reference import deepseek_v2 as ref

    m, a, ml = arch.moe, arch.attn, arch.attn.mla
    y = a.rope_scaling
    return ref.Dims(
        d=arch.d_model, vocab=arch.vocab_size, n_layers=arch.n_layers,
        n_dense_lead=m.first_k_dense, d_ff_dense=arch.d_ff, attn="mla",
        n_heads=a.n_heads, n_kv_heads=a.n_heads, head_dim=a.d_head,
        q_lora=ml.q_lora_rank, kv_lora=ml.kv_lora_rank, qk_nope=ml.qk_nope_dim,
        qk_rope=ml.qk_rope_dim, v_head=ml.v_head_dim, n_experts=m.n_experts,
        top_k=m.top_k, d_expert=m.d_expert, n_shared=m.n_shared,
        norm_topk=m.norm_topk_prob, routed_scale=m.routed_scaling_factor,
        rope_theta=a.rope_theta, eps=1e-6, n_held=m.n_held, held_offset=m.held_offset,
        n_group=m.n_group, topk_group=m.topk_group,
        yarn=(y.factor, y.original_max_position, y.beta_fast, y.beta_slow, y.mscale,
              y.mscale_all_dim))


def _reference_logits(w, dm, seq, mode):
    """The reference's full forward over one sequence: logits at every
    position."""
    from benchmark.reference import deepseek_v2 as ref
    from benchmark.reference.moe_transformer import Ops, rmsnorm

    rows, _ = ref.pack_rows([seq], len(seq))
    with jax.default_matmul_precision("highest"):
        h = ref.hidden_states(w, dm, rows, mode)[0]
        return np.asarray(Ops(mode).mm(rmsnorm(h, w["final_norm"]["scale"], dm.eps),
                                       w["w_out"]))


def test_engine_prefill_then_decode_matches_reference():
    """Float32 weights served through the engine (prefill, then decode
    through the latent cache) give the reference's logits at every served
    position.  Tolerance: 1e-4 of the logits' spread, for float32
    arithmetic in another order (chunked attention, absorbed latent decode,
    capacity dispatch) through 3 layers and the routers' softmax, which
    differs by about 1.5e-6 here; the reference itself in bfloat16 misses
    it by more than 10x."""
    arch = _arch(held=4, held_offset=4, exec_mode="dual_path_cost")
    lm = LM(arch, dtype=jnp.float32)
    w = _tree(lm, 2**32 + 11)
    eng = ServingEngine(lm, w, BatchingConfig(n_slots=2, max_seq=64))
    seen = []
    prefill, decode = eng._prefill_chunk, eng._decode

    def tap_prefill(*a):
        out = prefill(*a)
        seen.append(("prefill", np.asarray(out[0][1])[0, -1]))
        return out

    def tap_decode(*a):
        out = decode(*a)
        seen.append(("decode", np.asarray(out[0][1])[:, 0]))
        return out

    eng._prefill_chunk, eng._decode = tap_prefill, tap_decode
    prompt = list(np.random.default_rng(5).integers(1, arch.vocab_size, 11))
    req = Request(prompt=prompt, max_new_tokens=6)
    eng.submit(req)
    eng.run_until_done()
    got = [v if kind == "prefill" else v[req.slot] for kind, v in seen]
    assert len(got) == 6
    dm = _dims(arch)
    seq = prompt + req.generated[:-1]
    ref32 = _reference_logits(w, dm, seq, "f32")[len(prompt) - 1:]
    ref16 = _reference_logits(w, dm, seq, "bf16")[len(prompt) - 1:]
    tol = 1e-4 * float(ref32.std())
    err = np.abs(np.stack(got) - ref32).max()
    assert err < tol, (err, tol)
    assert np.abs(ref16 - ref32).max() > 10 * tol
    # the held experts took assignments in every layer
    assert eng.stats.routed_tokens > 0


@pytest.fixture
def _pallas(monkeypatch):
    monkeypatch.setenv("REPRO_DUAL_BACKEND", "pallas")


def test_held_share_reads_the_stacks_in_place(_pallas):
    """On the Pallas dual path the held share's kernels take the whole
    (L*held, ...) stacks, no (held, d, f) value is made, and the decode
    step matches the dense oracle; its counts cover the router's outputs,
    zero off the share."""
    from tests.test_expert_stack_in_place import _eqns, _kernel_weight_rows, _shapes

    held, off = 4, 8
    arch = _arch(held=held, held_offset=off, exec_mode="dual_path")
    lm = LM(arch, dtype=jnp.float32)
    n_moe = arch.n_layers - arch.moe.first_k_dense
    assert lm.moe_layers_in_place() == n_moe
    p = _tree(lm, 2**31 + 3)
    B = 6
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, 1), 1, 255),
             "position": jnp.arange(B, dtype=jnp.int32) + 3}
    cache = jax.tree.map(lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape, a.dtype),
                         lm.init_cache(B, 16))
    jaxpr = jax.make_jaxpr(lm.decode_step)(p, batch, cache).jaxpr
    assert set(_kernel_weight_rows(jaxpr, arch)) == {n_moe * held}
    d, f = arch.d_model, arch.moe.d_expert
    assert not {(held, d, f), (held, f, d)} & _shapes(jaxpr)
    assert any(e.primitive.name == "pallas_call" for e in _eqns(jaxpr))
    logits, _, aux = jax.jit(lm.decode_step)(p, batch, cache)
    dense = LM(_arch(held=held, held_offset=off), dtype=jnp.float32)
    ref_logits, _, ref_aux = jax.jit(dense.decode_step)(p, batch, cache)
    counts = np.asarray(aux.counts)
    assert counts.shape == (n_moe, E)
    assert not counts[:, :off].any() and not counts[:, off + held:].any()
    assert counts[:, off:off + held].sum() > 0
    np.testing.assert_array_equal(counts, np.asarray(ref_aux.counts))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-5, atol=1e-5)


def test_engine_schedules_the_held_experts():
    """The host scheduler pass and the cost tables run over the held
    experts; the engine records their count, the assignments they take
    each decode step, and the group selection has a named scope."""
    arch = _arch(held=4, held_offset=4, exec_mode="dual_path_cost")
    lm = LM(arch, dtype=jnp.float32)
    tel = Telemetry(capacity=1 << 14, enabled=True)
    eng = ServingEngine(lm, _tree(lm, 9), BatchingConfig(n_slots=2, max_seq=64),
                        telemetry=tel)
    assert eng.layer_spec.n_experts == 4
    assert eng._sieve_state.pim_time_by_count.ndim == 1
    assert tel.gauges()["engine/moe_experts_held"] == 4.0
    seen, held = [], []
    run_sieve = eng._run_sieve

    def tap(counts):
        seen.append(counts.shape)
        held.append(float(np.sum(counts)))
        return run_sieve(counts)

    eng._run_sieve = tap
    eng.submit(Request(prompt=[3, 4, 5, 6], max_new_tokens=5))
    eng.run_until_done()
    assert seen and set(seen) == {(2, 4)}
    steps = [e for e in tel.events() if e["name"] == "engine/moe_held_assignments"]
    assert len(steps) == len(seen)
    assert tel.counters()["engine/moe_held_assignments"] == sum(held) > 0
    B = 2
    text = jax.jit(lm.decode_step).lower(
        eng.params, {"tokens": jnp.ones((B, 1), jnp.int32),
                     "position": jnp.zeros((B,), jnp.int32), "sieve": eng._sieve_state},
        lm.init_cache(B, 64)).as_text(debug_info=True)
    assert "moe/group_route" in text


@pytest.mark.parametrize("held,held_offset", [(0, 0), (4, 4)], ids=["full", "share"])
def test_engine_counts_expert_streams(held, held_offset):
    """Each decode step's ``engine/moe_expert_streams`` is the number of
    (layer, expert) weight passes of the expert kernels: the experts with
    at least one assignment in the counts the step hands the scheduler,
    summed over the MoE layers, for the whole layer and for a held share."""
    arch = _arch(held=held, held_offset=held_offset, exec_mode="dual_path_cost")
    lm = LM(arch, dtype=jnp.float32)
    tel = Telemetry(capacity=1 << 14, enabled=True)
    eng = ServingEngine(lm, _tree(lm, 11), BatchingConfig(n_slots=2, max_seq=64),
                        telemetry=tel)
    streams = []
    run_sieve = eng._run_sieve

    def tap(counts):
        streams.append(float(np.sum(np.asarray(counts) > 0)))
        return run_sieve(counts)

    eng._run_sieve = tap
    eng.submit(Request(prompt=[3, 4, 5, 6], max_new_tokens=4))
    eng.submit(Request(prompt=[7, 8, 9], max_new_tokens=3))
    eng.run_until_done()
    # one sample a decode step, carrying the running total
    total = [e["value"] for e in tel.events()
             if e["name"] == "engine/moe_expert_streams"]
    steps = np.diff([0.0] + total).tolist()
    assert streams and steps == streams
    assert tel.counters()["engine/moe_expert_streams"] == sum(streams)
    # at most one pass per held expert of each of the 2 MoE layers
    assert sum(steps) > 0
    assert all(0 <= s <= 2 * eng.layer_spec.n_experts for s in steps)
