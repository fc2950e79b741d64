"""Qwen3-Next as one chip's share of an expert group through the harness:
the plain reference ``reference/qwen3_next.py`` against the served engine
at tiny widths (Gated DeltaNet and gated attention layers, 4 of 16 experts
held, a gated shared expert), its interface, the configuration at full
width, and the readers of its two metrics."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as runmod
from benchmark import serve, weights as wmod, work, work_hybrid
from benchmark.reference import qwen3_next as ref
from benchmark.run import load_json
from benchmark.tests import tiny

CONFIG = "qwen3-next-80b-a3b.8L-e64"

# one period of 3 linear + 1 gated attention layers; 16 router outputs,
# top-3 renormalised; this chip holds experts 4-7
TINY_QNEXT = {
    "name": "tiny-qnext", "source": "test", "reference": "qwen3_next",
    "repo_arch": "qwen3-next-80b-a3b",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "full_attention_interval": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_per_tok": 3, "num_hidden_layers": 4,
    "norm_topk_prob": True, "vocab_size": 256, "rope_theta": 10000000,
    "rms_norm_eps": 1e-06, "reduced": [],
    "expert_share": {"router_outputs": 16, "offset": 4},
    "program": {
        "dtype": "bfloat16",
        "overrides": {"n_layers": 4, "d_model": 64, "d_ff": 128, "vocab_size": 256},
        "attn_overrides": {"n_heads": 4, "n_kv_heads": 2, "d_head": 16, "rotary_dim": 4},
        "moe_overrides": {"n_experts": 16, "top_k": 3, "d_expert": 32, "held": 4,
                          "held_offset": 4, "capacity_factor": 16 / 3,
                          "expert_exec": "dual_path_cost"},
        "n_slots": 4, "max_seq": 128,
    },
}


@pytest.fixture
def tiny_linear(monkeypatch):
    """The program's qwen3-next arch with tiny Gated DeltaNet heads (a
    configuration file overrides top-level, attention and MoE sizes, not
    the linear-attention group)."""
    import repro.configs as configs

    get = configs.get_arch

    def get_arch(name):
        arch = get(name)
        if name != "qwen3-next-80b-a3b":
            return arch
        return dataclasses.replace(arch, linear=dataclasses.replace(
            arch.linear, n_k_heads=2, n_v_heads=4, d_k=16, d_v=16))

    monkeypatch.setattr(configs, "get_arch", get_arch)


def test_reference_has_the_interface_the_harness_calls():
    for name in ("dims_from_config", "served_gaps", "layer_stack", "layer", "pre_router",
                 "moe_out", "hidden_states", "head"):
        assert callable(getattr(ref, name)), name
    dm = ref.dims_from_config(load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json"))
    assert (dm.n_experts, dm.n_held, dm.held_offset, dm.top_k) == (512, 64, 0, 10)
    assert (dm.n_shared, dm.d_expert, dm.n_layers, dm.full_every) == (1, 512, 8, 4)
    assert (dm.lin_k_heads, dm.lin_v_heads, dm.lin_k_dim, dm.lin_v_dim) == (16, 32, 128, 128)
    assert (dm.n_heads, dm.n_kv_heads, dm.head_dim, dm.rotary_dim) == (16, 2, 256, 64)
    assert dm.rope_theta == 1e7 and dm.conv_width == 4
    hash(dm)  # a static argument of the jitted layers


def test_configuration_matches_the_program_tree():
    """The program holds the configuration's share at full width (abstract:
    nothing is allocated): the MoE of all 8 layers in one stack whose
    router keeps 512 outputs, the mixers in stacks of their own, and the
    sizes PERF.md reckons."""
    from repro.models import LM

    cfg = load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json")
    dm = ref.dims_from_config(cfg)
    arch = serve.build_arch(cfg)
    serve.check_arch(arch, dm)
    assert (arch.moe.n_held, arch.moe.held_offset) == (dm.n_held, dm.held_offset)
    lm = LM(arch, dtype=jnp.bfloat16)
    lay = lm.abstract_params()
    moe = lay["blocks"]["moe"]
    assert moe["w_router"].shape == (8, dm.d, 512)
    assert moe["w_gate"].shape == (8, 64, dm.d, dm.d_expert)

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    assert count(lay["linear_attn"]) == 6 * work_hybrid.linear_params(dm) + 6 * (
        2 * dm.lin_v_heads + dm.lin_v_dim)  # + A_log, dt_bias and the norm
    assert count(lay["full_attn"]) == 2 * (work_hybrid.full_params(dm) + 2 * dm.head_dim)
    held = 2 * count(lay)
    assert 5.0e9 < held < 5.1e9, held
    cache = jax.eval_shape(lambda: lm.init_cache(128, 2048))
    assert cache["recurrent"].shape == (6, 128, 32, 128, 128)
    assert cache["recurrent"].size * 4 == 6 * 128 * work_hybrid.state_elems(dm) * 4


def test_routing_shaping_finds_the_one_router_stack(tiny_linear):
    """``weights.shape_routing`` reads and rewrites ``blocks/moe/w_router``,
    one ``(L, d, router outputs)`` stack over the mixed layers."""
    from repro.models import LM

    dm = ref.dims_from_config(TINY_QNEXT)
    arch = serve.build_arch(TINY_QNEXT)
    serve.check_arch(arch, dm)
    lm = LM(arch, dtype=jnp.bfloat16)
    w, c_hat = wmod.make_weights(lm.abstract_params(), dm.d, 5)
    shaped, report = wmod.shape_routing(ref, w, c_hat, dm, tiny.ROUTING, 5)
    assert len(report["gains"]) == dm.n_layers == 4
    assert shaped["blocks"]["moe"]["w_router"].shape == (4, 64, 16)
    assert not np.allclose(np.asarray(shaped["blocks"]["moe"]["w_router"]),
                           np.asarray(w["blocks"]["moe"]["w_router"]))
    # the popularity term lands on the hot experts in every layer
    assert all(a > 0.5 for a in report["calibration_hot_share"])


def test_tiny_share_is_correct_and_both_metrics_read(tmp_path, tiny_linear):
    """Served at tiny widths through the engine, with a trace: the share
    agrees with the reference (bf16 serving against float32, under the
    tiny cells' limit), the reference in float8 in the program's place
    does not, and the two new metrics read a number or nothing."""
    root = tiny.make_root(tmp_path, [TINY_QNEXT], {"closed": tiny.TINY_CLOSED}, limit=0.002)
    keep = {}
    res = runmod.run(["--workload", "tiny-qnext.closed", "--seed", str(2**33 + 5),
                      "--seconds", "2", "--trace", "1"], require_tpu=False, root=root,
                     keep=keep, control="fp8")
    assert keep["gaps"].size >= 30
    assert res["correct"] is True
    assert res["control_correct"] is False
    assert res["failed"] == 0 and res["attempted"] > 0
    for name in ("gated_delta_roofline", "step_mfu_share_hybrid.decode"):
        v = res["metrics"].get(name)
        assert v is None or v["value"] > 0, (name, v)
    # the CPU trace has no TPU plane, so no kernel time to read
    assert "gated_delta_roofline" not in res["metrics"]


def span(name, t0, dur, value=float("nan")):
    return {"kind": "span", "name": name, "track": "main", "t0_ns": t0, "dur_ns": dur,
            "value": value}


def ctx_of(spans, counts=(), kv_lens=(), trace_window_s=0.0, dm=None):
    dm = dm or ref.dims_from_config(
        load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json"))
    peak = load_json(tiny.REPO / "benchmark" / "peaks.json")["devices"]["TPU v5 lite"]
    return runmod.Context(dm=dm, peak=peak, window_s=1.0, work={}, spans=spans,
                          trace_window_s=trace_window_s, decode_counts=list(counts),
                          decode_kv_lens=list(kv_lens))


def read(name, ctx):
    return runmod.metric_reader(tiny.REPO, name)(ctx)


@pytest.mark.parametrize("metric", ["gated_delta_roofline", "step_mfu_share_hybrid.decode"])
def test_hybrid_readers_none_without_their_work(metric):
    """Nothing traced, no decode step, or a configuration without linear
    layers (the accepted cells'): nothing to read."""
    assert read(metric, ctx_of([])) is None
    no_decode = [span("engine/step", 0, 1_000_000), span("engine/prefill", 0, 900_000, 128.0)]
    assert read(metric, ctx_of(no_decode, trace_window_s=0.25)) is None
    gqa = load_json(tiny.REPO / "benchmark" / "configs" / "qwen3-30b-a3b.8L.json")
    from benchmark.reference import moe_transformer

    steps = [span("engine/decode", 0, 1_000_000, 2.0)]
    ctx = ctx_of(steps, [np.ones((8, 128))], [[10, 20]], 0.25,
                 dm=moe_transformer.dims_from_config(gqa))
    assert read(metric, ctx) is None


def test_gated_delta_needs_are_the_state_twice():
    """128 rows over 6 linear layers move each row's 2.10 MB float32 state
    in and out: 3.22 GB a step, 3.93 ms at 819 GB/s; q, k, v, gates and
    output add 1.2%."""
    dm = ref.dims_from_config(load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json"))
    flops, nbytes = work_hybrid.gated_delta_needs(dm, 128)
    state = 6 * 128 * 32 * 128 * 128 * 4
    assert 2 * state * 1.011 < nbytes < 2 * state * 1.012
    assert flops == 6 * 128 * 6.0 * 32 * 128 * 128
    peak = load_json(tiny.REPO / "benchmark" / "peaks.json")["devices"]["TPU v5 lite"]
    assert work.needed_time(flops, nbytes, peak) == pytest.approx(nbytes / 819e9)


def test_step_mfu_share_hybrid_counts_layer_kinds():
    """Two traced decode steps of 128 rows at KV depth 300 and one prefill
    of 128 tokens in 0.25 s, with the held experts taking 2.5 assignments
    each: the count by layer kind, and more than linear layers counted as
    GQA would be for the gated attention's doubled q_proj."""
    dm = ref.dims_from_config(load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json"))
    ring = []
    for i in range(2):
        t = i * 100_000_000
        ring += [span("engine/step", t, 100_000_000),
                 span("engine/decode", t + 1_000_000, 80_000_000, 128.0)]
    prefills = [span("engine/prefill", 2_000_000, 9_000_000, 128.0),
                span("engine/prefill", 900_000_000, 9_000_000, 512.0)]
    held = np.zeros((8, 512))
    held[:, :64] = 2.5
    kv = [[300] * 128, [300] * 128]
    ctx = ctx_of(ring + prefills, [held, held], kv, 0.25)
    share = 2 * 8 * 64 * 2.5 / (256 * 10 * 8)
    want = work_hybrid.model_flops(dm, np.full(256, 300), [128], share)
    assert read("step_mfu_share_hybrid.decode", ctx) == pytest.approx(
        100.0 * want / (0.25 * 197e12))
    per_token = float(work_hybrid.token_flops(dm, 0, False, 0.0))
    attn_proj = 3 * dm.d * dm.n_heads * dm.head_dim + 2 * dm.d * dm.n_kv_heads * dm.head_dim
    assert per_token > 2.0 * 2 * attn_proj
