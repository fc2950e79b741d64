"""qwen3-next-80b-a3b — 48L d_model=2048, vocab=151936; three of every four
layers Gated DeltaNet linear attention (16 key / 32 value heads of 128,
conv 4), every fourth gated softmax attention (16 q / 2 kv heads of 256,
q/k RMSNorm, rope on 64 of 256 dims, theta 1e7, sigmoid output gate); MoE
in every layer: 512 experts of width 512, top-10 renormalised, plus one
shared expert of width 512 behind a sigmoid gate.
[hf:Qwen/Qwen3-Next-80B-A3B-Instruct config.json and modeling_qwen3_next]

The Sieve split applies end to end; the linear layers keep a fixed-size
float32 recurrent state per slot beside the full layers' KV cache.
Multi-token prediction (no key in the config) is not served.
"""

from .base import ArchConfig, AttnConfig, LinearAttnConfig, MoEConfig

CONFIG = ArchConfig(
    name="qwen3-next-80b-a3b",
    family="linear_hybrid",
    n_layers=48,
    d_model=2048,
    d_ff=5120,  # not used: every layer is MoE (d_expert below)
    vocab_size=151936,
    attn=AttnConfig(
        kind="gqa", n_heads=16, n_kv_heads=2, d_head=256, rope_theta=1e7,
        rotary_dim=64, qk_norm=True, out_gate=True,
    ),
    linear=LinearAttnConfig(
        n_k_heads=16, n_v_heads=32, d_k=128, d_v=128, conv_width=4,
        full_attention_interval=4,
    ),
    moe=MoEConfig(
        n_experts=512, top_k=10, d_expert=512, n_shared=1, shared_gate=True,
        norm_topk_prob=True, expert_exec="dual_path_cost",
    ),
    norm="rmsnorm",
    act="swiglu",
    pos="rope",
    source="hf:Qwen/Qwen3-Next-80B-A3B-Instruct",
)
