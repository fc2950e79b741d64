"""deepseek-v2-236b — 60L d_model=5120 128H, MLA kv_lora=512,
d_ff(expert)=1536, vocab=102400, MoE 2 shared + 160 routed top-6.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2 config.json]

First layer dense (d_ff=12288), remaining 59 MoE — per the DeepSeek-V2
paper.  Published routing (``topk_method: group_limited_greedy``): softmax
scores over the 160 experts, 8 groups of 20, each group scored by its best
expert; a token takes its top 6 experts from its best 3 groups, with the
softmax scores as weights, not renormalised (``norm_topk_prob: false``),
times ``routed_scaling_factor`` 16.  Published rope: YaRN with factor 40
over 4096 original positions (beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 0.707), so the attention softmax scale gains
(0.1 * 0.707 * ln 40 + 1)**2.  Sieve applies end-to-end; MLA's compressed
latent KV cache (kv_lora + rope = 576/token) makes this the
cheapest-cache arch per token.
"""

from .base import ArchConfig, AttnConfig, MLAConfig, MoEConfig, YarnConfig

CONFIG = ArchConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    d_ff=12288,  # the dense (first_k_dense) layers
    vocab_size=102400,
    attn=AttnConfig(
        kind="mla",
        n_heads=128,
        n_kv_heads=128,
        d_head=128,
        rope_theta=1e4,
        mla=MLAConfig(
            q_lora_rank=1536,
            kv_lora_rank=512,
            qk_nope_dim=128,
            qk_rope_dim=64,
            v_head_dim=128,
        ),
        rope_scaling=YarnConfig(
            factor=40.0,
            original_max_position=4096,
            beta_fast=32.0,
            beta_slow=1.0,
            mscale=0.707,
            mscale_all_dim=0.707,
        ),
    ),
    moe=MoEConfig(
        n_experts=160, top_k=6, d_expert=1536, n_shared=2, first_k_dense=1,
        n_group=8, topk_group=3, norm_topk_prob=False,
        routed_scaling_factor=16.0,
    ),
    norm="rmsnorm",
    act="swiglu",
    pos="rope",
    source="arXiv:2405.04434",
)
