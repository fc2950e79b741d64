"""Transformer blocks and layer stacks (scan-over-layers).

Block kinds:
  * ``attn_mlp``  — (GQA | MLA) attention + (dense MLP | MoE)   [most archs]
  * ``mamba``     — Mamba2 block                                 [zamba2]
  * ``rwkv``      — RWKV6 time-mix + channel-mix                 [rwkv6]
  * ``enc``/``dec`` — whisper encoder / decoder (w/ cross-attn)

Stacks scan over stacked per-layer params (HLO size O(1) in depth) with
optional ``jax.checkpoint`` for training.  Hybrid (zamba2) scans segments of
[shared attention block + (attn_every-1) mamba blocks].
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import attention as attn_lib
from . import ssm as ssm_lib
from .layers import apply_mlp, apply_norm, init_mlp, init_norm
from .moe import LOCAL_MESH, MeshInfo, MoEOut, init_moe, moe_block


class BlockAux(NamedTuple):
    """Per-layer auxiliary outputs surfaced to the trainer / Sieve engine."""

    moe_aux: jax.Array  # scalar load-balance loss (0 for non-MoE)
    counts: jax.Array  # (E,) expert token counts (zeros(1) for non-MoE)
    dropped: jax.Array  # scalar overflow-dropped tokens


def _zero_aux(n_experts: int = 1) -> BlockAux:
    return BlockAux(
        jnp.zeros((), jnp.float32),
        jnp.zeros((n_experts,), jnp.int32),
        jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# attn + (mlp | moe) block
# ---------------------------------------------------------------------------


def init_attn_mlp_block(
    key, arch: ArchConfig, moe: bool, dtype=jnp.bfloat16, d_ff: Optional[int] = None
) -> dict:
    ks = jax.random.split(key, 4)
    d = arch.d_model
    p = {"norm1": init_norm(d, arch.norm), "norm2": init_norm(d, arch.norm)}
    if arch.attn.kind == "mla":
        p["attn"] = attn_lib.init_mla(ks[0], arch.attn, d, dtype)
    else:
        p["attn"] = attn_lib.init_gqa(ks[0], arch.attn, d, dtype)
    if moe:
        p["moe"] = init_moe(ks[1], arch, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], d, d_ff or arch.d_ff, arch.act, dtype)
    return p


def attn_mlp_block_seq(
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,
    arch: ArchConfig,
    mi: MeshInfo,
    moe: bool,
    mrope_positions=None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    sieve=None,  # SieveState for expert_exec="dual_path_cost"
):
    """Full-sequence block (training / prefill).  Returns (x, cache, aux)."""
    h = apply_norm(p["norm1"], x, arch.norm)
    if arch.attn.kind == "mla":
        a, ckv, kr = attn_lib.mla_prefill(
            p["attn"], h, positions, arch.attn, q_chunk, kv_chunk
        )
        cache = (ckv, kr)
    else:
        a, k, v = attn_lib.gqa_prefill(
            p["attn"], h, positions, arch.attn,
            mrope_positions=mrope_positions, causal=True,
            q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        cache = (k, v)
    x = x + a
    h = apply_norm(p["norm2"], x, arch.norm)
    if moe:
        out: MoEOut = moe_block(p["moe"], h, arch, mi, sieve=sieve)
        x = x + out.y
        aux = BlockAux(out.aux_loss, out.counts, out.n_dropped)
    else:
        x = x + apply_mlp(p["mlp"], h, arch.act)
        aux = _zero_aux()
    return x, cache, aux


def attn_mlp_block_decode(
    p: dict,
    x: jax.Array,  # (B, 1, d)
    position: jax.Array,  # (B,)
    cache,  # (k, v) or (ckv, kr)
    arch: ArchConfig,
    mi: MeshInfo,
    moe: bool,
    mrope_positions=None,
    seq_par: bool = False,
    sieve=None,  # SieveState for expert_exec="dual_path_cost"
    paged=None,  # (block_tables, owner, block_pos) — cache is a block pool
):
    h = apply_norm(p["norm1"], x, arch.norm)
    if arch.attn.kind == "mla":
        a, ckv, kr = attn_lib.mla_decode(
            p["attn"], h, position, cache[0], cache[1], arch.attn
        )
        new_cache = (ckv, kr)
    elif paged is not None:
        a, k, v = attn_lib.gqa_decode_paged(
            p["attn"], h, position, cache[0], cache[1], paged, arch.attn,
            mrope_positions=mrope_positions, mi=mi,
        )
        new_cache = (k, v)
    elif seq_par:
        scales = (cache[2], cache[3]) if len(cache) == 4 else None  # int8 KV
        a, new_cache = attn_lib.gqa_decode_seqpar(
            p["attn"], h, position, cache[0], cache[1], arch.attn, mi,
            kv_scales=scales,
        )
    else:
        a, k, v = attn_lib.gqa_decode(
            p["attn"], h, position, cache[0], cache[1], arch.attn,
            mrope_positions=mrope_positions, mi=mi,
        )
        new_cache = (k, v)
    x = x + a
    h = apply_norm(p["norm2"], x, arch.norm)
    if moe:
        out: MoEOut = moe_block(p["moe"], h, arch, mi, sieve=sieve)
        x = x + out.y
        aux = BlockAux(out.aux_loss, out.counts, out.n_dropped)
    else:
        x = x + apply_mlp(p["mlp"], h, arch.act)
        aux = _zero_aux()
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# mamba / rwkv blocks
# ---------------------------------------------------------------------------


def init_mamba_block(key, arch: ArchConfig, dtype=jnp.bfloat16) -> dict:
    return {
        "norm": init_norm(arch.d_model, arch.norm),
        "mamba": ssm_lib.init_mamba2(key, arch.d_model, arch.ssm, dtype),
    }


def mamba_block(p, x, arch: ArchConfig, state, step: bool, mi=None):
    h = apply_norm(p["norm"], x, arch.norm)
    if step:
        y, new_state = ssm_lib.mamba2_step(p["mamba"], h, arch.ssm, state)
    else:
        y, new_state = ssm_lib.mamba2_seq(
            p["mamba"], h, arch.ssm, state, mesh_info=mi
        )
    return x + y, new_state, _zero_aux()


def init_rwkv_block(key, arch: ArchConfig, dtype=jnp.bfloat16) -> dict:
    return {
        "norm1": init_norm(arch.d_model, "layernorm"),
        "norm2": init_norm(arch.d_model, "layernorm"),
        "rwkv": ssm_lib.init_rwkv6(key, arch.d_model, arch.d_ff, arch.ssm, dtype),
    }


def rwkv_block(p, x, arch: ArchConfig, state):
    return ssm_lib.rwkv6_block_seq(
        p["rwkv"], x, arch.ssm, state, (p["norm1"], p["norm2"])
    )


# ---------------------------------------------------------------------------
# whisper encoder / decoder blocks
# ---------------------------------------------------------------------------


def init_enc_block(key, arch: ArchConfig, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 2)
    d = arch.d_model
    return {
        "norm1": init_norm(d, arch.norm),
        "attn": attn_lib.init_gqa(ks[0], arch.attn, d, dtype),
        "norm2": init_norm(d, arch.norm),
        "mlp": init_mlp(ks[1], d, arch.d_ff, arch.act, dtype),
    }


def enc_block(p, x, arch: ArchConfig, q_chunk=1024, kv_chunk=1024):
    h = apply_norm(p["norm1"], x, arch.norm)
    a, _, _ = attn_lib.gqa_prefill(
        p["attn"], h, None, arch.attn, causal=False,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )
    x = x + a
    h = apply_norm(p["norm2"], x, arch.norm)
    return x + apply_mlp(p["mlp"], h, arch.act)


def init_dec_block(key, arch: ArchConfig, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 3)
    d = arch.d_model
    return {
        "norm1": init_norm(d, arch.norm),
        "attn": attn_lib.init_gqa(ks[0], arch.attn, d, dtype),
        "norm_x": init_norm(d, arch.norm),
        "xattn": attn_lib.init_cross_attention(ks[1], arch.attn, d, dtype),
        "norm2": init_norm(d, arch.norm),
        "mlp": init_mlp(ks[2], d, arch.d_ff, arch.act, dtype),
    }


def dec_block_seq(p, x, positions, enc_kv, arch: ArchConfig, q_chunk=512, kv_chunk=512):
    """Decoder prefill: causal self-attn + cross-attn to encoder states."""
    h = apply_norm(p["norm1"], x, arch.norm)
    a, k, v = attn_lib.gqa_prefill(
        p["attn"], h, None, arch.attn, causal=True,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )  # whisper uses learned (additive) positions, no rope
    x = x + a
    h = apply_norm(p["norm_x"], x, arch.norm)
    x = x + attn_lib.cross_attention(p["xattn"], h, enc_kv[0], enc_kv[1], arch.attn)
    h = apply_norm(p["norm2"], x, arch.norm)
    return x + apply_mlp(p["mlp"], h, arch.act), (k, v)


def dec_block_decode(p, x, position, cache, enc_kv, arch: ArchConfig):
    h = apply_norm(p["norm1"], x, arch.norm)
    a, k, v = attn_lib.gqa_decode(
        p["attn"], h, position, cache[0], cache[1], arch.attn,
        use_rope=False,  # whisper uses learned (additive) positions
    )
    x = x + a
    h = apply_norm(p["norm_x"], x, arch.norm)
    x = x + attn_lib.cross_attention(p["xattn"], h, enc_kv[0], enc_kv[1], arch.attn)
    h = apply_norm(p["norm2"], x, arch.norm)
    return x + apply_mlp(p["mlp"], h, arch.act), (k, v)
