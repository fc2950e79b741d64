"""Chip smoke test: serve qwen3-moe-30b-a3b at published widths on a TPU.

    python chip_smoke.py            # one chip: kernels, serving, dense oracle
    python chip_smoke.py --chips 4  # the expert-parallel path over four chips,
                                    # compared with a one-chip run

One process owns the chip and every phase runs in it.  The model is the
repo's own serving path (``repro.launch.serve.build_engine`` ->
``ServingEngine``) at published widths in bf16, all 128 experts, the
depth cut to ``LAYERS`` (every layer of this model is MoE, so that is 8
whole periods), with random weights from ``SEED``.

Phases (one chip):

1. each Pallas kernel of the main path against ``repro.kernels.ref`` at
   the model's widths, compiled for the chip (``interpret=False``);
2. 4 requests x 128 prompt tokens x 16 new tokens served greedily through
   the engine, whose expert path ships as ``dual_path_cost``;
3. the first prefill and decode logits of that run against the same
   inputs replayed through an engine with ``expert_exec="dense"`` (the
   repo's oracle) on the same weights;
4. phases 2 and 3 for deepseek-v2-236b as one chip's share of an expert
   group: its dense lead layer and one MoE layer holding 20 of the
   router's 160 experts, published routing and rope.

Four chips: the same model with its experts split over a (data 1,
model 4) mesh.  Its first MoE layer alone is checked against the same
layer on one chip, then it serves the same requests and its first logits
are replayed through a one-chip engine of the same seed.

Any failed phase exits non-zero without the result line; no exception is
caught.  The last line of standard output is one JSON object naming the
device as JAX reports it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

BACKEND_SWITCHES = (
    "REPRO_PALLAS_INTERPRET",
    "REPRO_DUAL_BACKEND",
    "REPRO_FLASH_DECODE",
)
ARCH = "qwen3-moe-30b-a3b"
LAYERS = 8
SEED = 0
REQUESTS, PROMPT_LEN, MAX_NEW = 4, 128, 16
SLOTS, MAX_SEQ = 32, 2048
DEEPSEEK_HELD = 20  # one routing group of 8, as in the benchmark's deepseek cell

# Kernel vs reference: both end in one bf16 rounding of the output
# (half an ulp is 2^-9 of the value); the kernels also round the SwiGLU
# product to bf16 before the down projection and accumulate in another
# order.  1e-2 of the output's largest magnitude bounds those; a wrong
# tile, mask or expert index is an O(1) error.
KERNEL_TOL = 1e-2
# Logit checks, as relative L2 error over the compared rows.  Served vs
# the dense oracle on one chip: the paths round bf16 activations at
# different points in every layer, and a routing decision near a top-k tie
# can flip downstream of that drift.
LOGITS_TOL = 5e-2
# One MoE layer, expert-parallel over four chips vs one chip, same weights
# and input: routing is the same computation on both sides, and each
# token's output differs only in that the four chips round their bf16
# partial sums before adding them (a few 2^-9 relative roundings).  A
# wrong expert slice or offset is an O(1) error on the tokens it serves.
EP_LAYER_TOL = 1e-2
# The served logits, four chips vs one: those roundings, and those of the
# attention output projection's partial sums, compound over 8 layers of
# random weights and flip routing decisions near top-k ties.  Seed 0 reads
# 3.6e-2 (prefill) and 4.6e-2 (decode), 5.8e-2 with no capacity drops;
# this limit guards against gross faults only, the layer check above
# against placement.
EP_TOL = 1.5e-1


class Fail(SystemExit):
    def __init__(self, msg: str):
        print(f"FAIL: {msg}", file=sys.stderr)
        super().__init__(1)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Phase 1: kernels against kernels/ref.py at the model's widths
# ---------------------------------------------------------------------------


def _rel_max(out, exp) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    exp = np.asarray(exp, np.float32)
    if not np.isfinite(out).all():
        return float("inf")
    return float(np.abs(out - exp).max() / max(np.abs(exp).max(), 1e-30))


def kernel_checks(arch) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    E, d, f = arch.moe.n_experts, arch.d_model, arch.moe.d_expert
    H, Kv, dh = arch.attn.n_heads, arch.attn.n_kv_heads, arch.attn.d_head
    bf = jnp.bfloat16
    slots, max_seq = SLOTS, MAX_SEQ
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, scale=1.0):
        return (jax.random.normal(next(ks), shape) * scale).astype(bf)

    wg = normal((E, d, f), d**-0.5)
    wu = normal((E, d, f), d**-0.5)
    wd = normal((E, f, d), f**-0.5)
    C = 8  # decode capacity at 32 slots (min_capacity floor)
    buf = normal((E, C, d))
    sizes = jax.random.randint(next(ks), (E,), 0, C + 1)
    S = E  # one tail row per expert, as dual_tail_tokens=1 builds it
    toks = normal((S, d))
    eids = jax.random.permutation(next(ks), S).astype(jnp.int32) % E
    valid = (jax.random.uniform(next(ks), (S,)) < 0.8).astype(jnp.int32)
    q = normal((slots, H, dh))
    ck = normal((slots, Kv, max_seq, dh))
    cv = normal((slots, Kv, max_seq, dh))
    lens = jax.random.randint(next(ks), (slots,), 1, max_seq + 1)
    page = 64
    nb = max_seq // page
    perm = jax.random.permutation(next(ks), slots * nb) + 1
    table = perm.reshape(slots, nb).astype(jnp.int32)

    def to_pool(c):  # dense head-major cache -> block pool under ``table``
        pages = c.reshape(slots, Kv, nb, page, dh).transpose(0, 2, 1, 3, 4)
        pool = jnp.zeros((slots * nb + 1, Kv, page, dh), c.dtype)
        return pool.at[table.reshape(-1)].set(pages.reshape(-1, Kv, page, dh))

    pk, pv = to_pool(ck), to_pool(cv)

    cases = [
        ("swiglu_gmm_capacity",
         lambda: ops.swiglu_gmm_capacity(buf, wg, wu, wd, sizes, interpret=False),
         lambda: ref.fused_swiglu_gmm_ref(buf, wg, wu, wd, sizes)),
        ("swiglu_gemv",
         lambda: ops.swiglu_gemv(toks, wg, wu, wd, eids, valid, interpret=False),
         lambda: ref.fused_swiglu_gemv_ref(toks, wg, wu, wd, eids, valid)),
        ("decode_attention",
         lambda: ops.decode_attention(q, ck, cv, lens, interpret=False),
         lambda: ref.decode_attention_ref(q, ck, cv, lens)),
        ("decode_attention n_splits=4",
         lambda: ops.decode_attention(q, ck, cv, lens, n_splits=4,
                                      interpret=False),
         lambda: ref.decode_attention_ref(q, ck, cv, lens)),
        ("decode_attention_paged",
         lambda: ops.decode_attention_paged(q, pk, pv, table, lens,
                                            interpret=False),
         lambda: ref.decode_attention_paged_ref(q, pk, pv, table, lens)),
    ]
    for name, run, oracle in cases:
        t0 = time.perf_counter()
        out = jax.block_until_ready(run())
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            exp = oracle()
        err = _rel_max(out, exp)
        ok = err <= KERNEL_TOL
        log(f"kernel {name}: shape={tuple(out.shape)} max|err|/max|ref|="
            f"{err:.3e} tol={KERNEL_TOL:g} first_call_s={dt:.2f} "
            f"{'ok' if ok else 'FAILED'}")
        if not ok:
            raise Fail(f"kernel {name} differs from kernels/ref.py: {err:.3e}")


# ---------------------------------------------------------------------------
# Serving through the engine, with the first logits recorded
# ---------------------------------------------------------------------------


class Tap:
    """Wraps an engine's compiled step; keeps the inputs, logits and picked
    ids of the first ``keep`` calls as host arrays (everything else passes
    through)."""

    def __init__(self, fn, keep: int, inputs):
        self.fn, self.keep, self.inputs, self.calls = fn, keep, inputs, []

    def __call__(self, *args):
        out = self.fn(*args)
        if len(self.calls) < self.keep:
            import numpy as np

            ids, logits = out[0]
            self.calls.append((self.inputs(*args), np.asarray(logits),
                               np.asarray(ids)))
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _prefill_inputs(params, batch, cache, slot):
    import numpy as np

    return {"tokens": np.asarray(batch["tokens"]), "slot": int(slot)}


def _decode_inputs(params, batch, cache):
    import numpy as np

    return {"tokens": np.asarray(batch["tokens"]),
            "position": np.asarray(batch["position"])}


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def serve(engine, compile_report: bool):
    """Serve ``REQUESTS`` through ``engine``; returns (done, prefill tap,
    decode tap).  With ``compile_report`` the prefill and decode steps are
    first compiled ahead of time for their serving shapes, and their
    compile seconds and Pallas kernel counts are printed."""
    import numpy as np

    from repro.serving import Request

    arch = engine.lm.arch
    n_requests, prompt_len, max_new = REQUESTS, PROMPT_LEN, MAX_NEW
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, arch.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    if compile_report:
        sieve = {"sieve": engine._sieve_state} if engine.uses_cost_split else {}
        B = engine.cfg.n_slots
        t0 = time.perf_counter()
        pc = engine._prefill_chunk.lower(
            engine.params,
            {"tokens": np.zeros((1, prompt_len), np.int32), **sieve},
            engine.cache, np.int32(0),
        ).compile()
        t1 = time.perf_counter()
        dc = engine._decode.lower(
            engine.params,
            {"tokens": np.zeros((B, 1), np.int32),
             "position": np.zeros((B,), np.int32), **sieve},
            engine.cache,
        ).compile()
        t2 = time.perf_counter()
        n_pre, n_dec = _custom_calls(pc), _custom_calls(dc)
        log(f"compile: prefill[{prompt_len}] {t1 - t0:.1f}s "
            f"(tpu_custom_call={n_pre}), decode[{B} slots] {t2 - t1:.1f}s "
            f"(tpu_custom_call={n_dec})")
        if n_dec == 0:
            raise Fail("the compiled decode step holds no Pallas kernel")
        mem = dc.memory_analysis()
        log(f"decode step: argument_bytes={mem.argument_size_in_bytes} "
            f"temp_bytes={mem.temp_size_in_bytes}")

    pre = engine._prefill_chunk = Tap(engine._prefill_chunk, n_requests,
                                      _prefill_inputs)
    dec = engine._decode = Tap(engine._decode, 1, _decode_inputs)
    t0 = time.perf_counter()
    for p in prompts:
        engine.submit(Request(prompt=p, max_new_tokens=max_new,
                              arrival_time=time.perf_counter()))
    done = engine.run_until_done()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in done)
    log(f"served: requests={len(done)} new_tokens={n_tok} "
        f"prompt_tokens={engine.stats.prefill_tokens} wall_s={dt:.2f} "
        f"(first-call compiles included) moe_dropped={engine.stats.dropped_tokens}"
        f" of routed={engine.stats.routed_tokens}")
    if len(done) != n_requests or n_tok != n_requests * max_new:
        raise Fail(f"served {len(done)} requests / {n_tok} tokens, expected "
                   f"{n_requests} / {n_requests * max_new}")
    if len(pre.calls) != n_requests or len(dec.calls) != 1:
        raise Fail("the engine did not run the expected prefill/decode calls")
    return done, pre, dec


def replay(engine, pre: Tap, dec: Tap):
    """The recorded prefill and first decode inputs run through
    ``engine``'s own compiled steps; returns their logits."""
    import jax.numpy as jnp
    import numpy as np

    # a fresh engine's cost-split state is the one the recorded calls saw
    sieve = {"sieve": engine._sieve_state} if engine.uses_cost_split else {}
    outs = []
    for inp, *_ in pre.calls:
        (_, logits), engine.cache, _ = engine._prefill_chunk(
            engine.params, {"tokens": jnp.asarray(inp["tokens"]), **sieve},
            engine.cache, np.int32(inp["slot"]),
        )
        outs.append(np.asarray(logits))
    inp = dec.calls[0][0]
    (_, logits), engine.cache, _ = engine._decode(
        engine.params,
        {"tokens": jnp.asarray(inp["tokens"]),
         "position": jnp.asarray(inp["position"]), **sieve},
        engine.cache,
    )
    return outs, np.asarray(logits)


def compare_logits(label: str, pre: Tap, dec: Tap, ref_pre, ref_dec,
                   vocab: int, live_slots, tol: float) -> None:
    import numpy as np

    got_p = np.concatenate([c[1] for c in pre.calls])[..., :vocab]
    exp_p = np.concatenate(ref_pre)[..., :vocab]
    ids_p = np.concatenate([c[2] for c in pre.calls])
    got_d = dec.calls[0][1][live_slots, ..., :vocab]
    exp_d = ref_dec[live_slots, ..., :vocab]
    ids_d = dec.calls[0][2][live_slots]
    for phase, got, exp, ids in (("prefill", got_p, exp_p, ids_p),
                                 ("decode", got_d, exp_d, ids_d)):
        got = got.astype(np.float32).reshape(-1, vocab)
        exp = exp.astype(np.float32).reshape(-1, vocab)
        if not (np.isfinite(got).all() and np.isfinite(exp).all()):
            raise Fail(f"{label} {phase}: non-finite logits")
        # the step's own greedy pick is the host argmax of its logits
        if not (ids == got.argmax(-1)).all():
            raise Fail(f"{label} {phase}: the step's picked ids are not the "
                       "argmax of its logits")
        rel = float(np.linalg.norm(got - exp) / np.linalg.norm(exp))
        agree = float((got.argmax(-1) == exp.argmax(-1)).mean())
        ok = rel <= tol
        log(f"check {label} {phase} logits: rows={got.shape[0]} "
            f"rel_l2={rel:.3e} max_abs={np.abs(got - exp).max():.3e} "
            f"argmax_agree={agree:.2f} tol={tol:g} "
            f"{'ok' if ok else 'FAILED'}")
        if not ok:
            raise Fail(f"{label} {phase} logits rel_l2 {rel:.3e} > {tol}")


def delete(tree) -> None:
    """Drop device buffers now rather than at collection."""
    import jax

    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def one_chip(arch, batching) -> None:
    from repro.models import attention, moe
    from repro.kernels import ops

    log(f"backends: dual_path={moe._dual_backend()} "
        f"attention={attention._flash_decode_mode()} "
        f"interpret={ops._interpret_default()}")
    if (moe._dual_backend(), attention._flash_decode_mode(),
            ops._interpret_default()) != ("pallas", "kernel", False):
        raise Fail("the chip path did not select the Pallas kernels")

    kernel_checks(arch)
    served_vs_oracle(arch, batching, "served vs dense oracle")
    served_vs_oracle(deepseek_share(), batching, "deepseek share vs dense oracle")


def deepseek_share():
    """DeepSeek-V2 as one chip's share of an expert group, at published
    widths (group-limited routing, YaRN): the dense lead layer and one MoE
    layer holding ``DEEPSEEK_HELD`` of the router's 160 experts, dropless."""
    from repro.launch.serve import build_arch

    arch = build_arch("deepseek-v2-236b", full=True, layers=2)
    m = arch.moe
    arch = dataclasses.replace(arch, moe=dataclasses.replace(
        m, held=DEEPSEEK_HELD, expert_exec="dual_path_cost",
        capacity_factor=m.n_experts / m.top_k))
    log(f"arch={arch.name} layers={arch.n_layers} experts held "
        f"{arch.moe.n_held} of {m.n_experts} (groups {m.n_group}, top "
        f"{m.topk_group}) top_k={m.top_k} vocab={arch.vocab_size}")
    return arch


def served_vs_oracle(arch, batching, label: str) -> None:
    """Serve through the engine, then replay its first calls through an
    engine with ``expert_exec="dense"`` on the same weights."""
    import jax

    from repro.launch.serve import build_engine

    t0 = time.perf_counter()
    eng = build_engine(arch, batching, seed=SEED)
    jax.block_until_ready(eng.params)
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    log(f"params: {n_params} ({n_params * 2 / 1e9:.2f} GB bf16) built in "
        f"{time.perf_counter() - t0:.1f}s; MoE layers read in place: "
        f"{eng.lm.moe_layers_in_place()}")
    done, pre, dec = serve(eng, compile_report=True)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")

    params = eng.params
    delete((eng.cache, eng._sieve_state))
    del eng
    dense_arch = dataclasses.replace(
        arch, moe=dataclasses.replace(arch.moe, expert_exec="dense")
    )
    oracle = build_engine(dense_arch, batching, seed=SEED, params=params)
    ref_pre, ref_dec = replay(oracle, pre, dec)
    live = sorted(r.slot for r in done)
    compare_logits(label, pre, dec, ref_pre, ref_dec, arch.vocab_size, live,
                   LOGITS_TOL)
    delete((params, oracle.cache, oracle._sieve_state))


def ep_layer_check(arch, params, mi) -> None:
    """The first MoE layer on the sharded weights vs the same weights on
    one chip, for a decode-sized and a prompt-sized token batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.moe import LOCAL_MESH, moe_block

    dev0 = jax.devices()[0]
    layer = jax.tree.map(lambda w: w[0], params["blocks"]["moe"])
    local = jax.device_put(layer, dev0)
    ep_fn = jax.jit(lambda p, x: moe_block(p, x, arch, mi))
    one_fn = jax.jit(lambda p, x: moe_block(p, x, arch, LOCAL_MESH))
    for T in (SLOTS, PROMPT_LEN):
        x = jax.random.normal(jax.random.PRNGKey(SEED), (1, T, arch.d_model),
                              jnp.bfloat16)
        ep, one = ep_fn(layer, x), one_fn(local, jax.device_put(x, dev0))
        got = np.asarray(ep.y, np.float32)
        exp = np.asarray(one.y, np.float32)
        if not (np.isfinite(got).all() and np.isfinite(exp).all()):
            raise Fail(f"EP layer T={T}: non-finite output")
        rel = float(np.linalg.norm(got - exp) / np.linalg.norm(exp))
        same_routing = bool((np.asarray(ep.counts) == np.asarray(one.counts)).all())
        drops = (int(ep.n_dropped), int(one.n_dropped))
        ok = rel <= EP_LAYER_TOL and same_routing and drops[0] == drops[1]
        log(f"check EP layer 0, 4 chips vs 1 chip, tokens={T}: rel_l2={rel:.3e} "
            f"tol={EP_LAYER_TOL:g} same_routing={same_routing} "
            f"dropped={drops[0]}/{drops[1]} {'ok' if ok else 'FAILED'}")
        if not ok:
            raise Fail(f"EP layer T={T} differs from one chip")
    delete(local)


def four_chips(arch, batching) -> None:
    import jax

    from repro.launch.mesh import make_mesh, mesh_info_for
    from repro.launch.serve import build_engine

    devices = jax.devices()
    if len(devices) != 4:
        raise Fail(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = make_mesh((1, 4), ("data", "model"))
    mi = mesh_info_for(mesh, batching.n_slots)
    eng = build_engine(arch, batching, seed=SEED, mesh_info=mi)
    jax.block_until_ready(eng.params)

    experts = [eng.params["blocks"]["moe"][k]
               for k in ("w_gate", "w_up", "w_down")]
    total = sum(x.nbytes for x in experts)
    per_dev = {d.id: 0 for d in devices}
    for x in experts:
        for sh in x.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    log(f"mesh: {dict(mesh.shape)} experts per chip="
        f"{arch.moe.n_experts // mi.ep_size} expert_bytes_total={total}")
    for d in devices:
        st = d.memory_stats() or {}
        log(f"device {d.id}: bytes_in_use={st.get('bytes_in_use')} "
            f"expert_bytes={per_dev[d.id]} ({per_dev[d.id] / total:.3f} of all)")
    if per_dev[devices[0].id] >= total:
        raise Fail("device 0 holds the whole expert stack")

    ep_layer_check(arch, eng.params, mi)
    done, pre, dec = serve(eng, compile_report=False)
    delete((eng.cache, eng._sieve_state, eng.params))
    del eng

    one = build_engine(arch, batching, seed=SEED)
    ref_pre, ref_dec = replay(one, pre, dec)
    delete((one.cache, one._sieve_state, one.params))
    compare_logits("4 chips vs 1 chip", pre, dec, ref_pre, ref_dec,
                   arch.vocab_size, sorted(r.slot for r in done), EP_TOL)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    switches = [k for k in BACKEND_SWITCHES if k in os.environ]
    if switches:
        raise Fail(f"refusing to run with backend switches set: {switches}")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise Fail(f"found platform {dev.platform!r} ({dev.device_kind}); "
                   "this check needs a TPU")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.serve import build_arch, use_compile_cache
    from repro.serving import BatchingConfig

    log(f"compile cache: {use_compile_cache()}")
    arch = build_arch(ARCH, full=True, layers=LAYERS)
    a, m = arch.attn, arch.moe
    log(f"arch={arch.name} layers={arch.n_layers} d_model={arch.d_model} "
        f"heads={a.n_heads} kv_heads={a.n_kv_heads} d_head={a.d_head} "
        f"experts={m.n_experts} top_k={m.top_k} d_expert={m.d_expert} "
        f"vocab={arch.vocab_size} expert_exec={m.expert_exec} dtype=bfloat16 "
        f"seed={SEED}")
    batching = BatchingConfig(n_slots=SLOTS, max_seq=MAX_SEQ)
    (four_chips if args.chips == 4 else one_chip)(arch, batching)

    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
