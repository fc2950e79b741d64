"""Sharding rules: params / caches / activations -> PartitionSpec trees.

Rules are path+shape based so they survive arbitrary stacking (leading scan
dims map to None).  Divisibility is checked against the mesh so awkward
head/vocab counts (whisper 8 heads, granite vocab 49155) fall back to
replication or GSPMD padding instead of failing.

Scheme (DESIGN.md §5):
  * batch dims          -> ("pod", "data")
  * attention q/o heads -> "model" (TP); kv heads sharded only if divisible
  * dense FFN           -> "model" (column/row TP)
  * MoE experts (E,...) -> "model" (EP), router replicated
  * embeddings / logits -> vocab over "model"
  * mamba d_inner, rwkv heads -> "model"
  * norms, scalars      -> replicated
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def _pad_spec(base: Tuple, ndim: int) -> P:
    """Left-pad a trailing-dims spec with None for stacked leading dims."""
    pad = ndim - len(base)
    assert pad >= 0, (base, ndim)
    return P(*([None] * pad + list(base)))


def param_pspecs(
    abstract_params: Any,
    arch: ArchConfig,
    model_axis: Optional[str] = "model",
    model_size: int = 1,
    fsdp_axis: Optional[str] = None,
    fsdp_size: int = 1,
    fsdp_min_bytes: int = 1 << 23,
) -> Any:
    """PartitionSpec tree matching the params tree from LM.init.

    ``fsdp_axis``: additionally shard large tensors over this (data) axis —
    ZeRO/FSDP-style.  GSPMD inserts the per-layer gathers at use sites;
    optimizer states inherit the spec, so fp32 moments shard too (this is
    what makes 236B-scale training fit 16 GB/chip).
    """

    def _apply_fsdp(spec: P, leaf) -> P:
        if fsdp_axis is None or fsdp_size <= 1:
            return spec
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        if nbytes < fsdp_min_bytes:
            return spec
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        # prefer the last unsharded divisible dim (contiguity)
        for i in range(len(leaf.shape) - 1, -1, -1):
            if entries[i] is None and leaf.shape[i] % fsdp_size == 0:
                entries[i] = fsdp_axis
                return P(*entries)
        return spec

    def rule(path, leaf) -> P:
        name = _path_str(path)
        shape = leaf.shape
        nd = len(shape)
        m = model_axis

        def shard_last_if(div_dim=-1):
            return (
                _pad_spec((None, m), nd)
                if m and shape[div_dim] % max(model_size, 1) == 0
                else _pad_spec((None, None), nd)
            )

        def shard_first_of_last2():
            return (
                _pad_spec((m, None), nd)
                if m and shape[-2] % max(model_size, 1) == 0
                else _pad_spec((None, None), nd)
            )

        if m is None or model_size <= 1:
            return P(*([None] * nd))

        # ---- embeddings / head -------------------------------------
        if name == "embed":
            return P(m, None) if shape[0] % model_size == 0 else P(None, None)
        if name == "w_out":
            return P(None, m) if shape[1] % model_size == 0 else P(None, None)
        if name.endswith("dec_pos"):
            return P(None, None)

        # ---- MoE ----------------------------------------------------
        if "/moe/" in name or name.startswith("moe/"):
            if "w_router" in name:
                return P(*([None] * nd))
            if "/shared/" in name:
                if name.endswith("w_down"):
                    return shard_first_of_last2()
                return shard_last_if()
            # expert tensors: (..., E, d, f) — shard E (3rd-from-last)
            if nd >= 3 and shape[-3] % model_size == 0:
                return _pad_spec((m, None, None), nd)
            return P(*([None] * nd))

        # ---- attention ----------------------------------------------
        if any(k in name for k in ("/attn/", "/xattn/")):
            last = name.rsplit("/", 1)[-1]
            if last in ("wq", "w_uq", "w_uk", "w_uv"):
                return shard_last_if()
            if last in ("wk", "wv"):
                return shard_last_if()
            if last == "wo":
                return shard_first_of_last2()
            if last in ("bq", "bk", "bv"):
                return shard_last_if()
            if last in ("w_dq", "w_dkv", "w_kr"):
                return P(*([None] * nd))  # small lora-down projections
            return P(*([None] * nd))

        # ---- dense MLP ------------------------------------------------
        last = name.rsplit("/", 1)[-1]
        if last in ("w_up", "w_gate"):
            return shard_last_if()
        if last == "w_down":
            return shard_first_of_last2()

        # ---- mamba ----------------------------------------------------
        if "/mamba/" in name:
            if last == "w_in":
                return shard_last_if()
            if last == "w_out":
                return shard_first_of_last2()
            return P(*([None] * nd))

        # ---- rwkv -----------------------------------------------------
        if "/rwkv/" in name:
            if last in ("w_r", "w_k", "w_v", "w_g", "w_ck", "w_cr", "wA"):
                return shard_last_if()
            if last in ("w_o", "w_cv", "wB"):
                return shard_first_of_last2()
            if last == "u" and shape[-2] % model_size == 0:
                return _pad_spec((m, None), nd)
            return P(*([None] * nd))

        # norms, scalars, conv kernels, everything else: replicate
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _apply_fsdp(rule(path, leaf), leaf), abstract_params
    )


def cache_pspecs(
    abstract_cache: Any,
    arch: ArchConfig,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: Optional[str] = "model",
    model_size: int = 1,
) -> Any:
    """Cache sharding: batch over data axes; heads over model if divisible.

    Cache leaves are stacked head-major attention KV (L, B, K, T, dh) with
    int8 scales (L, B, K, T), MLA latents (L, B, T, c), whisper's
    cross-attention KV (L, B, Se, H, dh), or pytrees of SSM states
    (L, B, H, ...).
    """
    dp = data_axes if data_axes else None

    def rule(path, leaf) -> P:
        name = _path_str(path)
        shape = leaf.shape
        nd = len(shape)
        is_ssm_state = any(s in name for s in ("wkv", "ssm", "conv", "x_tm", "x_cm", "mamba"))
        div = lambda n: bool(model_axis) and n % max(model_size, 1) == 0
        if nd == 5 and name.startswith("cross"):
            # encoder KV (L, B, Se, H, dh): heads over the model axis
            return P(None, dp, None, model_axis if div(shape[3]) else None, None)
        is_kv = "attn" in name or "self" in name or "blocks" in name or "prefix" in name
        if nd in (4, 5) and is_kv and not is_ssm_state and arch.attn.kind != "mla":
            # (L, B, K, T[, dh]): prefer head sharding (TP); when the kv head
            # count doesn't divide the model axis (GQA kv=4/8 on 16-way TP),
            # shard the sequence dim instead — the cache then fits, at the
            # price of per-layer gather collectives (quantified in §Roofline
            # and attacked in §Perf with sequence-parallel decode attention).
            tail = (None,) * (nd - 4)
            if div(shape[2]):
                return P(None, dp, model_axis, None, *tail)
            return P(None, dp, None, model_axis if div(shape[3]) else None, *tail)
        if nd == 4 and "blocks" in name and not is_ssm_state:
            # MLA latent (L, B, T, c) — shard the sequence dim
            return P(None, dp, model_axis if div(shape[2]) else None, None)
        # SSM states: (L, B, H, P, N) / (L, B, W, C) / (L, B, D) / rwkv wkv.
        # Zamba2's segment states carry two leading stack dims:
        # (nseg, per, B, ...).
        n_stack = 2 if "mamba_seg" in name else 1
        if nd >= n_stack + 1:
            spec = [None] * n_stack + [dp] + [None] * (nd - n_stack - 1)
            h_dim = n_stack + 1
            if (
                nd >= h_dim + 2
                and model_axis
                and ("wkv" in name or "ssm" in name)
                and shape[h_dim] % max(model_size, 1) == 0
            ):
                spec[h_dim] = model_axis  # heads dim (mamba ssm, rwkv wkv)
            return P(*spec)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, abstract_cache)


def batch_pspecs(batch_specs: Any, data_axes: Tuple[str, ...]) -> Any:
    """Inputs: shard the batch dim over the data axes.

    tokens/labels (B, S); position (B,); mrope (3, B, S); embeds (B, S, d).
    """
    dp = data_axes if data_axes else None

    def rule(path, leaf) -> P:
        name = _path_str(path)
        nd = len(leaf.shape)
        if name.endswith("mrope_positions"):
            return P(None, dp, *([None] * (nd - 2)))
        return P(dp, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch_specs)


def to_shardings(mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree)
