"""Foundational layers: norms, MLPs, embeddings, RoPE / M-RoPE.

Pure-functional: every layer is ``f(params, x, ...) -> y`` with params as
plain dicts of jnp arrays.  Compute runs in the activation dtype (bf16 by
default) with fp32 islands where numerics demand it (norm statistics,
softmax, rotary phases).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _he(key, shape, scale, dtype):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return (jax.random.normal(key, shape) * (scale / jnp.sqrt(fan_in))).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(d: int, kind: str, dtype=jnp.float32) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(params: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(f"unknown norm {kind!r}")
    y = y * params["scale"].astype(jnp.float32)
    if kind == "layernorm":
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, act: str, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _he(ks[0], (d_model, d_ff), 1.0, dtype),
        "w_down": _he(ks[1], (d_ff, d_model), 1.0, dtype),
    }
    if act == "swiglu":
        p["w_gate"] = _he(ks[2], (d_model, d_ff), 1.0, dtype)
    return p


def apply_mlp(params: dict, x: jax.Array, act: str) -> jax.Array:
    up = x @ params["w_up"]
    if act == "swiglu":
        gate = x @ params["w_gate"]
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(f"unknown act {act!r}")
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def lm_logits(
    h: jax.Array, table: jax.Array, w_out: Optional[jax.Array]
) -> jax.Array:
    """Project to the vocabulary.  ``w_out`` is None for tied embeddings."""
    if w_out is not None:
        return h @ w_out
    return h @ table.T


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, d: int, theta: float, n_pos: int) -> float:
    return (d * math.log(n_pos / (rotations * 2 * math.pi))) / (2 * math.log(theta))


def rope_freqs(d_head: int, theta: float, scaling=None) -> jax.Array:
    """Rope inverse frequencies; with a :class:`YarnConfig` the YaRN ones:
    extrapolated below the correction dim of ``beta_fast``, interpolated
    (divided by ``factor``) above that of ``beta_slow``, a linear ramp
    between."""
    inv = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    if scaling is None:
        return inv
    n_pos = scaling.original_max_position
    low = max(math.floor(_yarn_correction_dim(scaling.beta_fast, d_head, theta, n_pos)), 0)
    high = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, d_head, theta, n_pos)),
               d_head - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d_head // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def rope_mscale(scaling) -> float:
    """Scale of rope's cos and sin under YaRN (1 without scaling)."""
    if scaling is None:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
        scaling.factor, scaling.mscale_all_dim)


def softmax_mscale(scaling) -> float:
    """Factor on the attention softmax scale under YaRN (1 without)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(
    x: jax.Array,  # (..., seq, heads, d_head)
    positions: jax.Array,  # (..., seq)
    theta: float,
    scaling=None,  # YarnConfig
) -> jax.Array:
    d = x.shape[-1]
    inv = rope_freqs(d, theta, scaling)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., seq, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    m = rope_mscale(scaling)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def apply_mrope(
    x: jax.Array,  # (batch, seq, heads, d_head)
    positions: jax.Array,  # (3, batch, seq): temporal / height / width
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the d_head/2 frequency slots are split into
    (temporal, height, width) sections, each rotated by its own position
    stream.  Text tokens carry identical t/h/w positions, reducing M-RoPE to
    standard RoPE for them."""
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    inv = rope_freqs(d, theta)  # (d/2,)
    # section id per frequency slot: 0..2
    sec = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (d/2,)
    # per-slot positions: pick the right stream  (batch, seq, d/2)
    pos = jnp.take_along_axis(
        positions.transpose(1, 2, 0).astype(jnp.float32),  # (b, s, 3)
        sec[None, None, :].astype(jnp.int32),
        axis=-1,
    )
    ang = pos * inv  # (b, s, d/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_positions(n_pos: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (encoder)."""
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * dim / d_model))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
