"""DeepSeek-V2 as one chip's share of an expert group: the plain reference
``reference/deepseek_v2.py`` against the served engine at tiny widths
(published group-limited routing and YaRN, a held share of the experts),
its interface, the work counts of the configuration at full width, and the
readers of the held share's metrics."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as runmod
from benchmark import serve, work
from benchmark.reference import deepseek_v2 as ref
from benchmark.run import load_json
from benchmark.tests import tiny

CONFIG = "deepseek-v2-236b.5L-e20"

# 16 router outputs in 4 groups of 4, top-3 from the best 2 groups, scores
# times 16; this chip holds experts 4-7 (group 1) of each MoE layer
TINY_DSV2 = {
    "name": "tiny-dsv2", "source": "test", "reference": "deepseek_v2",
    "repo_arch": "deepseek-v2-236b",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "first_k_dense_replace": 1,
    "num_experts_per_tok": 3, "num_hidden_layers": 3, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 16,
    "topk_method": "group_limited_greedy", "vocab_size": 256, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-06, "reduced": [],
    "expert_share": {"router_outputs": 16, "offset": 4},
    "program": {
        "dtype": "bfloat16",
        "overrides": {"n_layers": 3, "d_model": 64, "d_ff": 128, "vocab_size": 256},
        "attn_overrides": {"n_heads": 4, "n_kv_heads": 4, "d_head": 16},
        "mla_overrides": {"q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_dim": 16,
                          "qk_rope_dim": 8, "v_head_dim": 16},
        "moe_overrides": {"n_experts": 16, "top_k": 3, "d_expert": 32, "n_shared": 1,
                          "first_k_dense": 1, "n_group": 4, "topk_group": 2,
                          "held": 4, "held_offset": 4, "capacity_factor": 16 / 3,
                          "expert_exec": "dual_path_cost"},
        "n_slots": 4, "max_seq": 128,
    },
}


def test_reference_has_the_interface_the_harness_calls():
    for name in ("dims_from_config", "served_gaps", "layer_stack", "layer", "pre_router",
                 "moe_out", "head"):
        assert callable(getattr(ref, name)), name
    dm = ref.dims_from_config(load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json"))
    assert (dm.n_experts, dm.n_held, dm.held_offset) == (160, 20, 0)
    assert (dm.n_group, dm.topk_group, dm.top_k) == (8, 3, 6)
    assert (dm.norm_topk, dm.routed_scale) == (False, 16.0)
    assert dm.yarn == (40.0, 4096.0, 32.0, 1.0, 0.707, 0.707)
    hash(dm)  # a static argument of the jitted layers


def test_configuration_matches_the_program_tree():
    """The counts from published sizes agree with the program's parameter
    tree at full width (abstract: nothing is allocated), and the program
    holds the configuration's share."""
    from repro.models import LM

    cfg = load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json")
    dm = ref.dims_from_config(cfg)
    arch = serve.build_arch(cfg)
    serve.check_arch(arch, dm)
    assert (arch.moe.n_held, arch.moe.held_offset) == (dm.n_held, dm.held_offset)
    assert arch.attn.rope_scaling.factor == dm.yarn[0]
    lay = LM(arch, dtype=jnp.bfloat16).abstract_params()
    moe = lay["blocks"]["moe"]
    assert moe["w_router"].shape == (dm.n_moe_layers, dm.d, 160)
    assert moe["w_gate"].shape == (dm.n_moe_layers, 20, dm.d, dm.d_expert)
    attn = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(lay["blocks"]["attn"])
               if a.ndim >= 3) // dm.n_moe_layers
    assert attn == work.attn_params(dm)
    expert = sum(int(np.prod(moe[k].shape)) for k in ("w_gate", "w_up", "w_down"))
    assert expert == dm.n_moe_layers * dm.n_held * work.expert_params(dm)
    # bytes of what the chip holds, as the configuration's sizing states
    held_bytes = 2 * sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(lay))
    assert 7.9e9 < held_bytes < 8.3e9, held_bytes


def test_tiny_share_is_correct_and_its_control_is_not(tmp_path):
    """Served at tiny widths through the engine, the held share agrees with
    the reference (bf16 serving against float32: every served token is the
    reference's best or within bf16 rounding of it), and the reference in
    float8 in the program's place does not, under the tiny cells' limit."""
    root = tiny.make_root(tmp_path, [TINY_DSV2], {"closed": tiny.TINY_CLOSED}, limit=0.002)
    keep = {}
    res = runmod.run(["--workload", "tiny-dsv2.closed", "--seed", str(2**33 + 5),
                      "--seconds", "2", "--trace", "0"], require_tpu=False, root=root,
                     keep=keep, control="fp8")
    assert keep["gaps"].size >= 30
    assert keep["gaps"].max() < 0.05, keep["gaps"].max()
    assert res["correct"] is True
    assert res["control_correct"] is False
    assert res["failed"] == 0 and res["attempted"] > 0


def span(name, t0, dur, value=float("nan")):
    return {"kind": "span", "name": name, "track": "main", "t0_ns": t0, "dur_ns": dur,
            "value": value}


def ring(n_steps):
    """``n_steps`` decode steps of 128 rows."""
    out = []
    for i in range(n_steps):
        t = i * 100_000_000
        out += [span("engine/step", t, 100_000_000),
                span("engine/decode", t + 1_000_000, 80_000_000, 128.0),
                span("engine/decode_launch", t + 1_000_000, 2_000_000),
                span("engine/sieve_host", t + 90_000_000, 2_000_000)]
    return out


def counts(held):
    """Per-step (MoE layers, router outputs) counts, zero off experts 0-19,
    whose held experts took ``held`` assignments in all."""
    out = []
    for h in held:
        c = np.zeros((4, 160))
        c[:, :20] = h / 80
        out.append(c)
    return out


def ctx_of(spans, held=(), kv_lens=(), trace_window_s=0.0):
    dm = ref.dims_from_config(load_json(tiny.REPO / "benchmark" / "configs" / f"{CONFIG}.json"))
    peak = load_json(tiny.REPO / "benchmark" / "peaks.json")["devices"]["TPU v5 lite"]
    return runmod.Context(dm=dm, peak=peak, window_s=1.0, work={}, spans=spans,
                          trace_window_s=trace_window_s, decode_counts=counts(held),
                          decode_kv_lens=list(kv_lens))


def read(name, ctx):
    return runmod.metric_reader(tiny.REPO, name)(ctx)


@pytest.mark.parametrize("metric", ["held_expert_load.decode", "step_mfu_share.decode"])
def test_held_share_readers_none_without_decode(metric):
    assert read(metric, ctx_of([])) is None
    no_decode = [span("engine/step", 0, 1_000_000), span("engine/prefill", 0, 900_000, 128.0)]
    assert read(metric, ctx_of(no_decode, trace_window_s=0.25)) is None


def test_held_expert_load_reads_the_ring():
    # 128 rows x 6 / 160 = 4.8 assignments a held expert, 20 held, 4 layers
    assert read("held_expert_load.decode", ctx_of(ring(2), [384, 384])) == pytest.approx(4.8)
    assert read("held_expert_load.decode", ctx_of(ring(2), [320, 480])) == pytest.approx(5.0)


def test_step_mfu_share_counts_the_held_assignments():
    """Two traced decode steps of 128 rows at KV depth 300 and one prefill
    of 128 tokens, in 0.25 s."""
    prefills = [span("engine/prefill", 2_000_000, 9_000_000, 128.0),
                span("engine/prefill", 900_000_000, 9_000_000, 512.0)]
    kv = [[300] * 128, [300] * 128]
    ctx = ctx_of(ring(2) + prefills, [384, 384], kv, 0.25)
    dm = ctx.dm
    share = 768.0 / (256 * 6 * 4)  # held assignments over the rows' routed ones
    routed_per_token = 2.0 * work.expert_params(dm) * dm.top_k * dm.n_moe_layers
    decode_kv = np.full(256, 300)
    flops = work.model_flops(dm, decode_kv, [128]) - (1 - share) * routed_per_token * (256 + 128)
    want = 100.0 * flops / (0.25 * 197e12)
    assert read("step_mfu_share.decode", ctx) == pytest.approx(want)
    # every assignment held: the whole model's count over the same seconds
    full = ctx_of(ring(2) + prefills, [3072, 3072], kv, 0.25)
    whole = 100.0 * work.model_flops(dm, decode_kv, [128]) / (0.25 * 197e12)
    assert read("step_mfu_share.decode", full) == pytest.approx(whole)
    assert math.isfinite(want) and want > 0
    # no traced decode step: nothing to read
    ctx.decode_kv_lens = []
    assert read("step_mfu_share.decode", ctx) is None
