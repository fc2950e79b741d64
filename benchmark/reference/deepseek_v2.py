"""Plain reference of DeepSeek-V2 as one chip's share of an expert group,
from its config file.

DeepSeek-V2 as published (hf deepseek-ai/DeepSeek-V2 config.json and its
modeling code): multi-head latent attention with YaRN rope (inverse
frequencies ramped between the correction dims of ``beta_fast`` and
``beta_slow``, cos and sin scaled by mscale(mscale) / mscale(mscale_all_dim),
the softmax scale by mscale(mscale_all_dim)**2), a dense lead layer, then
MoE layers whose router scores all ``router_outputs`` experts by softmax and
gives each token its top-k from its ``topk_group`` best groups of experts
(a group scores its best expert), with the scores as weights times
``routed_scaling_factor`` (``norm_topk_prob`` false), plus shared experts.

The configuration holds ``n_routed_experts`` of the router's experts,
starting at ``expert_share.offset``: the routed part of each MoE layer is
what those experts give for the tokens routed to them, as on one chip of
the deployment the file states; shared experts and everything outside the
expert layer are whole.  The one departure from the published code is
rope's layout: it rotates the two halves of the rope dims where the
published code rotates interleaved pairs, which is the same function under
a fixed permutation of the ``w_kr`` and ``w_uq`` rope columns.

Written out plainly, as ``moe_transformer`` (whose arithmetic modes,
attention core, packing and LM head this module uses): causal attention
over whole rows, every token through every held expert with its routing
weight (zero off its top-k), no cache, no capacity, no kernels.  The
interface is the one ``benchmark/run.py`` and ``benchmark/weights.py``
call: ``dims_from_config``, ``served_gaps``, ``layer_stack``, ``layer``,
``pre_router``, ``moe_out`` and ``head``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import moe_transformer as base
from benchmark.reference.moe_transformer import F32, Ops, head, pack_rows, rmsnorm, swiglu


@dataclass(frozen=True)
class Dims(base.Dims):
    """``n_experts`` is the router's width; ``n_held`` experts are held,
    from ``held_offset``."""

    n_held: int = 0
    held_offset: int = 0
    n_group: int = 1
    topk_group: int = 1
    # (factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim)
    yarn: Optional[Tuple[float, ...]] = None


def dims_from_config(cfg: Dict) -> Dims:
    """Sizes from the published config keys; ``n_routed_experts`` counts
    the experts held, ``expert_share.router_outputs`` the router's width."""
    b = base.dims_from_config(cfg)
    share = cfg.get("expert_share", {})
    rs = cfg.get("rope_scaling")
    yarn = None
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {rs.get('type')!r}: only yarn is written here")
        yarn = tuple(float(rs[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
            "mscale", "mscale_all_dim"))
    return Dims(**{**dataclasses.asdict(b),
                   "n_experts": int(share.get("router_outputs", b.n_experts))},
                n_held=b.n_experts, held_offset=int(share.get("offset", 0)),
                n_group=int(cfg.get("n_group") or 1),
                topk_group=int(cfg.get("topk_group") or 1), yarn=yarn)


# ---------------------------------------------------------------------------
# YaRN rope (DeepseekV2YarnRotaryEmbedding, yarn_get_mscale)
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(D: int, theta: float, yarn) -> jax.Array:
    extra = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    if yarn is None:
        return extra
    factor, n_pos, fast, slow = yarn[:4]

    def corr_dim(rot):
        return D * math.log(n_pos / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(fast)), 0)
    high = min(math.ceil(corr_dim(slow)), D - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(D // 2, dtype=F32) - low) / (high - low), 0, 1)
    keep_extra = 1.0 - ramp
    return extra / factor * (1.0 - keep_extra) + extra * keep_extra


def softmax_scale(dm: Dims) -> float:
    s = 1.0 / math.sqrt(dm.qk_nope + dm.qk_rope)
    if dm.yarn is not None and dm.yarn[5]:
        s *= yarn_mscale(dm.yarn[0], dm.yarn[5]) ** 2
    return s


def rope(x, pos, dm: Dims):
    """Rotate-half rope with YaRN; x (S, heads, D), pos (S,)."""
    D = x.shape[-1]
    ang = pos.astype(F32)[:, None] * inv_freq(D, dm.rope_theta, dm.yarn)[None, :]
    m = 1.0 if dm.yarn is None else (yarn_mscale(dm.yarn[0], dm.yarn[4])
                                     / yarn_mscale(dm.yarn[0], dm.yarn[5]))
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# Layers (one row of S positions holding one or more requests)
# ---------------------------------------------------------------------------


def mla(ops, p, x, pos, seg, dm: Dims):
    S = x.shape[0]
    H, nope, r = dm.n_heads, dm.qk_nope, dm.qk_rope
    cq = rmsnorm(ops.mm(x, p["w_dq"]), p["q_norm_scale"], dm.eps)
    q = ops.mm(cq, p["w_uq"]).reshape(S, H, nope + r)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, dm)], -1)
    ckv = rmsnorm(ops.mm(x, p["w_dkv"]), p["kv_norm_scale"], dm.eps)
    k_pe = rope(ops.mm(x, p["w_kr"])[:, None, :], pos, dm)
    k_nope = ops.mm(ckv, p["w_uk"]).reshape(S, H, nope)
    v = ops.mm(ckv, p["w_uv"]).reshape(S, H, dm.v_head)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, r))], -1)
    o = base._attend(ops, q, k, v, seg, softmax_scale(dm))
    return ops.mm(o.reshape(S, H * dm.v_head), p["wo"])


def route(ops, w_router, x, dm: Dims):
    """Softmax over all the router's experts, top-k from each token's
    ``topk_group`` best groups; returns dense (S, n_experts) combine
    weights and the top-k indices."""
    probs = jax.nn.softmax(ops.mm(x, w_router), axis=-1)
    S, E = probs.shape
    G = dm.n_group
    if G > 1:
        best = probs.reshape(S, G, E // G).max(-1)
        _, gi = jax.lax.top_k(best, dm.topk_group)
        in_group = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], gi].set(True)
        probs = jnp.where(jnp.repeat(in_group, E // G, axis=1), probs, 0.0)
    top_p, top_i = jax.lax.top_k(probs, dm.top_k)
    if dm.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    else:
        top_p = top_p * dm.routed_scale
    dense = jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], top_i].set(top_p)
    return dense, top_i


def moe(ops, p, i, x, dm: Dims, w_router=None):
    """Routed part of MoE layer ``i`` of the stacked params ``p`` from the
    held experts (every token through each, weighted by its combine weight,
    zero off its top-k), plus the shared experts."""
    if p["w_gate"].shape[1] != dm.n_held:
        raise ValueError(f"the weights hold {p['w_gate'].shape[1]} experts a layer; "
                         f"the configuration says {dm.n_held}")
    if w_router is None:
        w_router = p["w_router"][i]
    cw, top_i = route(ops, w_router, x, dm)
    cw = cw[:, dm.held_offset:dm.held_offset + dm.n_held]
    eb = base._expert_block(dm.n_held)

    def sl(w, b):
        return jax.lax.dynamic_slice(w, (i, b * eb, 0, 0), (1, eb) + w.shape[2:])[0]

    def body(y, b):
        wg, wu, wd = sl(p["w_gate"], b), sl(p["w_up"], b), sl(p["w_down"], b)
        c = jax.lax.dynamic_slice_in_dim(cw, b * eb, eb, axis=1)
        h = jax.nn.silu(ops.ein("sd,edf->sef", x, wg)) * ops.ein("sd,edf->sef", x, wu)
        return y + ops.ein("sef,efd->sd", h * c[:, :, None], wd), None

    y, _ = jax.lax.scan(body, jnp.zeros(x.shape, F32), jnp.arange(dm.n_held // eb))
    if dm.n_shared:
        y = y + swiglu(ops, jax.tree.map(lambda a: a[i], p["shared"]), x)
    return y, top_i


def pre_mlp(ops, stack, i, x, pos, seg, dm: Dims):
    """Residual after layer ``i``'s attention, and the input of its MLP."""
    a = jax.tree.map(lambda w: w[i], stack["attn"])
    x = x + mla(ops, a, rmsnorm(x, stack["norm1"]["scale"][i], dm.eps), pos, seg, dm)
    return x, rmsnorm(x, stack["norm2"]["scale"][i], dm.eps)


@functools.partial(jax.jit, static_argnames=("dm", "mode", "is_moe"))
def layer(stack, i, x, pos, seg, *, dm: Dims, mode: str, is_moe: bool):
    """Block ``i`` of a stacked block tree on one row: x (S, d) float32 ->
    (x, top-k indices)."""
    ops = Ops(mode)
    x, h = pre_mlp(ops, stack, i, x, pos, seg, dm)
    if is_moe:
        y, top_i = moe(ops, stack["moe"], i, h, dm)
    else:
        y = swiglu(ops, jax.tree.map(lambda w: w[i], stack["mlp"]), h)
        top_i = jnp.zeros((x.shape[0], 0), jnp.int32)
    return x + y, top_i


@functools.partial(jax.jit, static_argnames=("dm",))
def pre_router(stack, i, x, pos, seg, *, dm: Dims):
    """Residual after MoE block ``i``'s attention and its router's input,
    in bfloat16 arithmetic (the routing calibration's forward)."""
    return pre_mlp(Ops("bf16"), stack, i, x, pos, seg, dm)


@functools.partial(jax.jit, static_argnames=("dm",))
def moe_out(stack, i, w_router, x, u, *, dm: Dims):
    """The rest of MoE block ``i`` in bfloat16 with router ``w_router``:
    (residual out, top-k indices over the router's experts)."""
    y, top_i = moe(Ops("bf16"), stack["moe"], i, u, dm, w_router=w_router)
    return x + y, top_i


layer_stack = base.layer_stack


def hidden_states(weights, dm: Dims, rows, mode: str):
    """Final hidden states (before the final norm) of each packed row."""
    out = []
    for toks, pos, seg in rows:
        x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0).astype(F32)
        pos_j, seg_j = jnp.asarray(pos), jnp.asarray(seg)
        for i in range(dm.n_layers):
            stack, j, is_moe = layer_stack(weights, i, dm)
            x, _ = layer(stack, j, x, pos_j, seg_j, dm=dm, mode=mode, is_moe=is_moe)
        out.append(x)
    return out


def served_gaps(weights, dm: Dims, requests, row_len: int,
                control: Optional[str] = None, chunk: int = 256):
    """For each served token of each request (prompt, generated), the gap
    by which the float32 reference's logit of that token lies below the
    reference's best at the position that produced it.  With ``control``
    set (a precision mode), the gap of the token that the control puts
    first, on the same positions.  Returns (gaps, control gaps or None,
    argmax agreement)."""
    with jax.default_matmul_precision("highest"):
        return _served_gaps(weights, dm, requests, row_len, control, chunk)


def _served_gaps(weights, dm: Dims, requests, row_len: int, control, chunk: int):
    seqs = [list(p) + list(g[:-1]) for p, g in requests]
    rows, place = pack_rows(seqs, row_len)
    sel = [(r, off + len(prompt) - 1 + i, int(t))
           for (prompt, gen), (r, off) in zip(requests, place) for i, t in enumerate(gen)]
    sel_r, sel_p, toks = (np.asarray(c) for c in zip(*sel))
    scale, w_out = weights["final_norm"]["scale"], weights["w_out"]

    def at_served(mode):
        return jnp.stack(hidden_states(weights, dm, rows, mode))[
            jnp.asarray(sel_r), jnp.asarray(sel_p)]

    h_ref = at_served("f32")
    h_ctl = at_served(control) if control else None
    gaps, cgaps, agree = [], [], []
    for c0 in range(0, len(toks), chunk):
        n = len(toks[c0:c0 + chunk])
        pad = ((0, chunk - n), (0, 0))
        tk = jnp.asarray(np.pad(toks[c0:c0 + chunk], (0, chunk - n)))
        mx, am, at = head(scale, w_out, jnp.pad(h_ref[c0:c0 + chunk], pad), tk,
                          dm=dm, mode="f32")
        gaps.append(np.asarray(mx - at)[:n])
        agree.append(np.asarray(am)[:n] == toks[c0:c0 + chunk])
        if h_ctl is not None:
            _, c_am, _ = head(scale, w_out, jnp.pad(h_ctl[c0:c0 + chunk], pad), tk,
                              dm=dm, mode=control)
            _, _, c_at = head(scale, w_out, jnp.pad(h_ref[c0:c0 + chunk], pad), c_am,
                              dm=dm, mode="f32")
            cgaps.append(np.asarray(mx - c_at)[:n])
    return (np.concatenate(gaps), np.concatenate(cgaps) if cgaps else None,
            float(np.concatenate(agree).mean()))
