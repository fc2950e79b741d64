"""Architecture configuration schema + registry + input-shape catalog.

Every assigned architecture ships as one ``<id>.py`` file exporting
``CONFIG``; this module holds the dataclasses, the shape catalog
(train_4k / prefill_32k / decode_32k / long_500k) and the registry.
"""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention dims."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek-V2's ``rope_scaling``, type "yarn"):
    the inverse frequencies ramp from interpolated (divided by ``factor``)
    to extrapolated between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position`` positions; cos
    and sin are scaled by mscale(mscale) / mscale(mscale_all_dim) and the
    attention softmax by mscale(mscale_all_dim)**2."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class AttnConfig:
    kind: str = "gqa"  # "gqa" | "mla" | "none"
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    qkv_bias: bool = False
    rope_theta: float = 1e6
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YarnConfig] = None  # MLA rope only
    # Qwen2-VL M-RoPE: head-dim split across (temporal, height, width)
    mrope_sections: Optional[Tuple[int, int, int]] = None


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    first_k_dense: int = 0  # leading dense layers (DeepSeek-V2: 1)
    capacity_factor: float = 1.25
    # Decode batches are tiny; a capacity floor keeps serving drop-free
    # (cap = min(T, min_capacity) lower bound).
    min_capacity: int = 8
    router_aux_coef: float = 0.01
    # Sieve integration — expert execution path:
    #   "dense"     — one dense einsum over the full (E, C, d) capacity
    #                 buffer (the bit-level reference oracle);
    #   "dual_path" — runtime sieve split: popular ("head") experts run as
    #                 grouped GEMMs, 1-few-token ("tail") experts stream
    #                 through the expert GEMV — the TPU adaptation of the
    #                 paper's GPU/PIM split.  The head/tail boundary is the
    #                 fixed dual_tail_tokens threshold;
    #   "dual_path_cost" — same executor, but the boundary comes from the
    #                 learned cost model (scheduler_jax.dual_path_split_cost
    #                 over a SieveState: the engine-exported EMA cost table
    #                 + packed SieveParams, refreshed on the EMA cadence
    #                 without recompiling the decode step) — the paper's
    #                 per-step count-driven GPU/PIM decision, in-graph.
    expert_exec: str = "dense"
    # Dual-path knobs (ignored under expert_exec="dense"):
    # tail threshold tau: experts with <= tau buffered rows take the
    # streaming-GEMV path (paper's PIM side).
    dual_tail_tokens: int = 1
    # Head compaction budget H: the grouped-GEMM path runs over the top-H
    # experts' capacity slabs instead of all E (the sieve "GPU set" size).
    # 0 = no budget (H = E): exact for any routing at dense-grouped cost.
    # With 0 < H < E, rows of experts beyond both the budget and the tail
    # threshold are dropped and counted in MoEOut.n_dropped (same contract
    # as capacity overflow).
    dual_max_head: int = 0
    # Routing (DeepSeek-V2's MoEGate): with n_group > 1 the experts form
    # n_group equal groups, a group scores its best expert, and a token's
    # top_k come from its topk_group best groups.  norm_topk_prob
    # renormalises the top_k weights to sum 1; otherwise they are the
    # softmax scores times routed_scaling_factor.
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # The share of the experts held here, one chip's part of an
    # expert-parallel group run without its exchange: experts
    # [held_offset, held_offset + n_held) of the router's n_experts.
    # held = 0 holds every expert.
    held: int = 0
    held_offset: int = 0

    def __post_init__(self):
        E, G = self.n_experts, self.n_group
        if G < 1 or E % G:
            raise ValueError(
                f"MoEConfig: n_experts={E} must split into n_group={G} "
                "equal routing groups"
            )
        if not 1 <= self.topk_group <= G:
            raise ValueError(
                f"MoEConfig: topk_group={self.topk_group} must lie in [1, "
                f"n_group={G}]"
            )
        if self.top_k > self.topk_group * (E // G):
            raise ValueError(
                f"MoEConfig: top_k={self.top_k} exceeds the "
                f"{self.topk_group * (E // G)} experts of topk_group="
                f"{self.topk_group} groups"
            )
        if self.held < 0 or self.held_offset < 0 or (
            self.held_offset + self.n_held > E
        ):
            raise ValueError(
                f"MoEConfig: held experts [{self.held_offset}, "
                f"{self.held_offset + self.n_held}) lie outside the router's "
                f"{E}"
            )

    @property
    def n_held(self) -> int:
        """Experts whose weights this layer holds."""
        return self.held or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    kind: str = "mamba2"  # "mamba2" | "rwkv6"
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    n_groups: int = 1
    # rwkv6
    decay_lora: int = 64
    wkv_chunk: int = 128


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # "dense" | "moe" | "hybrid" | "ssm" | "audio" | "vlm"
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attn: AttnConfig = field(default_factory=AttnConfig)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    act: str = "swiglu"  # "swiglu" | "gelu"
    pos: str = "rope"  # "rope" | "mrope" | "learned" | "none"
    tie_embeddings: bool = False
    # encoder-decoder (whisper)
    encdec: bool = False
    enc_layers: int = 0
    enc_seq: int = 1500  # encoder positions for the decode shapes (whisper)
    # hybrid (zamba2): one shared attention+MLP block applied every
    # ``attn_every`` backbone blocks (weights shared across applications)
    attn_every: int = 0
    # modality frontends are stubs by assignment: input_specs() yields
    # precomputed frame/patch embeddings instead of raw audio/pixels
    modality_stub: Optional[str] = None  # "audio_frames" | "vision_patches"
    source: str = ""  # provenance note
    notes: str = ""

    # ------------------------------------------------------------------
    @property
    def supports_decode(self) -> bool:
        return True  # all assigned archs have a decoder

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid archs)."""
        return self.family in ("ssm", "hybrid")

    @property
    def is_moe(self) -> bool:
        return self.moe is not None

    def param_count(self) -> float:
        """Approximate parameter count (embeddings + blocks)."""
        d, ff, V = self.d_model, self.d_ff, self.vocab_size
        n_emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0.0
        a = self.attn
        if a.kind == "gqa":
            per_layer += d * a.n_heads * a.d_head * 2 + 2 * d * a.n_kv_heads * a.d_head
        elif a.kind == "mla":
            m = a.mla
            per_layer += (
                d * m.q_lora_rank
                + m.q_lora_rank * a.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
                + d * (m.kv_lora_rank + m.qk_rope_dim)
                + m.kv_lora_rank * a.n_heads * (m.qk_nope_dim + m.v_head_dim)
                + a.n_heads * m.v_head_dim * d
            )
        if self.moe is not None:
            n_mats = 3 if self.act == "swiglu" else 2
            per_layer += (self.moe.n_experts + self.moe.n_shared) * (
                n_mats * d * self.moe.d_expert
            ) + self.moe.n_experts * d
        else:
            n_mats = 3 if self.act == "swiglu" else 2
            per_layer += n_mats * d * ff
        if self.ssm is not None and self.ssm.kind == "mamba2":
            di = self.ssm.expand * d
            per_layer = 2 * d * di + di * d  # rough
        return n_emb + self.n_layers * per_layer

    def reduced(self, **overrides) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw: dict = dict(
            n_layers=min(self.n_layers, 2 if not self.encdec else 2),
            d_model=64,
            d_ff=128,
            vocab_size=256,
        )
        a = self.attn
        if a.kind != "none":
            kw["attn"] = dataclasses.replace(
                a,
                n_heads=4,
                n_kv_heads=min(max(a.n_kv_heads, 1), 2) if a.kind == "gqa" else 0,
                d_head=16,
                mla=MLAConfig(
                    q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16,
                )
                if a.mla is not None
                else None,
                mrope_sections=(4, 2, 2) if a.mrope_sections else None,
            )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=8, top_k=2, d_expert=32,
                n_shared=min(self.moe.n_shared, 1),
                first_k_dense=min(self.moe.first_k_dense, 1),
                n_group=min(self.moe.n_group, 4),
                topk_group=min(self.moe.topk_group, 2),
            )
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=16, decay_lora=8, wkv_chunk=16
            )
        if self.encdec:
            kw["enc_layers"] = 2
            kw["enc_seq"] = 16
        if self.attn_every:
            kw["attn_every"] = 2
            kw["n_layers"] = 5
        kw.update(overrides)
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = (
    "qwen3-moe-30b-a3b",
    "deepseek-v2-236b",
    "zamba2-7b",
    "deepseek-coder-33b",
    "granite-3-2b",
    "qwen1.5-0.5b",
    "granite-3-8b",
    "whisper-base",
    "qwen2-vl-7b",
    "rwkv6-7b",
)

_MODULE_OF = {
    "qwen3-moe-30b-a3b": "qwen3_moe_30b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "zamba2-7b": "zamba2_7b",
    "deepseek-coder-33b": "deepseek_coder_33b",
    "granite-3-2b": "granite_3_2b",
    "qwen1.5-0.5b": "qwen15_0_5b",
    "granite-3-8b": "granite_3_8b",
    "whisper-base": "whisper_base",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "rwkv6-7b": "rwkv6_7b",
}


def get_arch(name: str) -> ArchConfig:
    if name not in _MODULE_OF:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULE_OF)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_OF[name]}")
    return mod.CONFIG


def all_archs() -> Dict[str, ArchConfig]:
    return {name: get_arch(name) for name in ARCH_IDS}


def cell_is_skipped(arch: ArchConfig, shape: ShapeSpec) -> Optional[str]:
    """Return a skip reason for (arch x shape), or None if the cell runs.

    Per the brief: long_500k needs sub-quadratic attention — run for
    SSM/hybrid archs, skip for pure full-attention archs (reason recorded
    in DESIGN.md §Arch-applicability and EXPERIMENTS.md).
    """
    if shape.name == "long_500k" and not arch.subquadratic:
        return (
            "long_500k requires sub-quadratic attention; "
            f"{arch.name} is a pure full-attention arch"
        )
    return None
