"""Operations and bytes the algorithm needs, from shapes and counts.

Independent of how the program implements the work: model FLOPs count
2 per multiply-add of every weight a token uses plus attention over its
KV depth; the expert path's needs come from the per-step expert counts;
attention's from the live KV lengths.  All sizes in bf16 bytes (2).
``dm`` is the sizes a reference module's ``dims_from_config`` gives.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np


BYTES = 2  # bf16


def attn_params(dm) -> int:
    d, H = dm.d, dm.n_heads
    if dm.attn == "mla":
        return (d * dm.q_lora + dm.q_lora * H * (dm.qk_nope + dm.qk_rope)
                + d * dm.kv_lora + d * dm.qk_rope
                + dm.kv_lora * H * (dm.qk_nope + dm.v_head) + H * dm.v_head * d)
    return 2 * d * H * dm.head_dim + 2 * d * dm.n_kv_heads * dm.head_dim


def expert_params(dm) -> int:
    """One routed expert's SwiGLU weights."""
    return 3 * dm.d * dm.d_expert


def layer_active_params(dm, moe: bool) -> int:
    """Weights one token multiplies by in one layer."""
    p = attn_params(dm)
    if moe:
        p += dm.top_k * expert_params(dm) + dm.n_shared * expert_params(dm)
        p += dm.d * dm.n_experts  # router
    else:
        p += 3 * dm.d * dm.d_ff_dense
    return p


def attn_flops(dm, kv_len) -> np.ndarray:
    """Score and value FLOPs of one token over ``kv_len`` positions, one
    layer."""
    if dm.attn == "mla":
        per = dm.n_heads * (dm.qk_nope + dm.qk_rope + dm.v_head)
    else:
        per = 2 * dm.n_heads * dm.head_dim
    return 2.0 * per * np.asarray(kv_len, np.float64)


def token_flops(dm, kv_len, head: bool) -> np.ndarray:
    """Model FLOPs of one token (or an array of tokens) at KV depth kv_len."""
    kv = np.asarray(kv_len, np.float64)
    f = 2.0 * (dm.n_dense_lead * layer_active_params(dm, False)
               + dm.n_moe_layers * layer_active_params(dm, True))
    f = f + dm.n_layers * attn_flops(dm, kv)
    if head:
        f = f + 2.0 * dm.d * dm.vocab
    return f


def model_flops(dm, decode_kv: Iterable[int], prefill_lens: Iterable[int]) -> float:
    """Decode tokens at their KV depths (with the LM head) plus whole
    prompts (the LM head once, for the last position)."""
    total = float(np.sum(token_flops(dm, np.asarray(list(decode_kv), np.float64), True)))
    for P in prefill_lens:
        total += float(np.sum(token_flops(dm, np.arange(1, P + 1), False)))
        total += 2.0 * dm.d * dm.vocab
    return total


def expert_needs(dm, counts: np.ndarray):
    """(flops, bytes) the routed experts need for per-layer counts (L, E):
    2*3*d*f per assignment; the weights of every expert with an
    assignment, and each assignment's token row in and out."""
    counts = np.asarray(counts, np.int64)
    assignments = float(counts.sum())
    active = float((counts > 0).sum())
    flops = 2.0 * expert_params(dm) * assignments
    nbytes = BYTES * (active * expert_params(dm) + 2.0 * dm.d * assignments)
    return flops, nbytes


def kv_bytes(dm, kv_lens: Iterable[int]) -> float:
    """Live K and V bytes one decode step reads over all GQA layers."""
    n = float(np.sum(np.asarray(list(kv_lens), np.float64)))
    return BYTES * 2.0 * n * dm.n_kv_heads * dm.head_dim * dm.n_layers


def needed_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
