"""Plain reference of Qwen3-Next as one chip's share of an expert group,
from its config file.

Qwen3-Next as published (hf Qwen/Qwen3-Next-80B-A3B-Instruct config.json
and ``modeling_qwen3_next``): layer ``i`` is gated softmax attention where
``(i + 1) % full_attention_interval == 0`` and Gated DeltaNet linear
attention otherwise; every layer is MoE.

* Gated DeltaNet: ``[q, k, v, z] = x W_qkvz`` and ``[b, a] = x W_ba``;
  q, k and v pass a causal depthwise conv of ``linear_conv_kernel_dim``
  taps and SiLU; ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``;
  ``g = -exp(A_log) softplus(a + dt_bias)``, ``beta = sigmoid(b)``; per
  value head (key head ``h // 2``) the state ``S`` (``d_k x d_v``) takes
  ``S <- exp(g) S``, ``S <- S + k (beta (v - S^T k))^T`` and reads out
  ``o = S^T q``, token by token in float32, reset where a packed row's
  segment starts; ``out = (RMSNorm(o) * w * SiLU(z)) W_o``.
* Gated attention: q and a gate from ``q_proj``, RMSNorm of q and k over
  the head dim, rotate-half rope on the first ``partial_rotary_factor`` of
  each head, GQA softmax attention, the output times ``sigmoid(gate)``
  before ``o_proj``.
* MoE: softmax over all ``router_outputs`` experts, top-k renormalised
  (``norm_topk_prob``); the held experts' part of the routed output, plus
  ``sigmoid(x w) * SwiGLU_shared(x)``.

The configuration holds ``num_experts`` of the router's experts from
``expert_share.offset``: as on one chip of the deployment the file states,
the routed part of each layer is what those experts give, and everything
else is whole.  Norm weights are read as stored (the published
zero-centred ``1 + w`` is a stored ``scale`` of ones).  Departures from
the published code, each a fixed relabelling of weights: ``W_qkvz`` and
``W_ba`` lay their columns out flat (all q, all k, all v, all z; all b,
all a) where the checkpoint groups them per key head; ``q_proj``'s
per-head ``[q, gate]`` halves are two matrices, ``wq`` and ``wq_gate``.
Multi-token prediction is not served, so it is not here either.

Written out plainly, with ``moe_transformer``'s arithmetic modes,
attention core, routing, packing and LM head: no cache, no capacity, no
kernels, no batching.  The interface is the one ``benchmark/run.py`` and
``benchmark/weights.py`` call.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import moe_transformer as base
from benchmark.reference.moe_transformer import F32, HIGHEST, Ops, head, pack_rows, rmsnorm, swiglu


@dataclass(frozen=True)
class Dims(base.Dims):
    """``n_experts`` is the router's width; ``n_held`` experts are held,
    from ``held_offset``.  The ``lin_*`` sizes are the Gated DeltaNet
    heads; ``rotary_dim`` the rope dims of a gated-attention head."""

    n_held: int = 0
    held_offset: int = 0
    lin_k_heads: int = 0
    lin_v_heads: int = 0
    lin_k_dim: int = 0
    lin_v_dim: int = 0
    conv_width: int = 0
    full_every: int = 0
    rotary_dim: int = 0


def dims_from_config(cfg: Dict) -> Dims:
    """Sizes from the published config keys; ``num_experts`` counts the
    experts held, ``expert_share.router_outputs`` the router's width; one
    shared expert where ``shared_expert_intermediate_size`` is set."""
    b = base.dims_from_config(cfg)
    share = cfg.get("expert_share", {})
    return Dims(**{**dataclasses.asdict(b),
                   "n_experts": int(share.get("router_outputs", b.n_experts)),
                   "n_shared": 1 if cfg.get("shared_expert_intermediate_size") else 0},
                n_held=b.n_experts, held_offset=int(share.get("offset", 0)),
                lin_k_heads=int(cfg["linear_num_key_heads"]),
                lin_v_heads=int(cfg["linear_num_value_heads"]),
                lin_k_dim=int(cfg["linear_key_head_dim"]),
                lin_v_dim=int(cfg["linear_value_head_dim"]),
                conv_width=int(cfg["linear_conv_kernel_dim"]),
                full_every=int(cfg["full_attention_interval"]),
                rotary_dim=int(b.head_dim * float(cfg.get("partial_rotary_factor", 1.0))))


def is_full(i: int, dm: Dims) -> bool:
    return (i + 1) % dm.full_every == 0


# ---------------------------------------------------------------------------
# Mixers (one row of S positions holding one or more requests)
# ---------------------------------------------------------------------------


def _l2norm(u):
    return u * jax.lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)


def gated_delta(ops, p, x, pos, seg, dm: Dims):
    S = x.shape[0]
    Hk, Hv, dk, dv, w = (dm.lin_k_heads, dm.lin_v_heads, dm.lin_k_dim, dm.lin_v_dim,
                         dm.conv_width)
    n_qkv = 2 * Hk * dk + Hv * dv
    qkvz = ops.mm(x, p["w_qkvz"])
    mixed, z = qkvz[:, :n_qkv], qkvz[:, n_qkv:]
    # causal depthwise conv within each request: tap o reads the input
    # w - 1 - o positions back
    conv = jnp.zeros_like(mixed)
    for o in range(w):
        back = w - 1 - o
        prev = jnp.concatenate([jnp.zeros((back, n_qkv), F32), mixed[:S - back]])
        same = jnp.concatenate([jnp.full((back,), jnp.iinfo(jnp.int32).min, seg.dtype),
                                seg[:S - back]]) == seg
        conv = conv + jnp.where(same[:, None], prev, 0.0) * p["conv_w"][o].astype(F32)
    conv = jax.nn.silu(conv)
    r = Hv // Hk
    q = jnp.repeat(_l2norm(conv[:, :Hk * dk].reshape(S, Hk, dk)), r, axis=1) / np.sqrt(dk)
    k = jnp.repeat(_l2norm(conv[:, Hk * dk:2 * Hk * dk].reshape(S, Hk, dk)), r, axis=1)
    v = conv[:, 2 * Hk * dk:].reshape(S, Hv, dv)
    ba = ops.mm(x, p["w_ba"])
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["A_log"][0].astype(F32)) * jax.nn.softplus(
        ba[:, Hv:] + p["dt_bias"][0].astype(F32))
    start = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])

    def token(s, t):
        qt, kt, vt, gt, bt, st = t
        s = jnp.where(st, 0.0, s) * jnp.exp(gt)[:, None, None]
        kv = jnp.einsum("hkv,hk->hv", s, kt, precision=HIGHEST)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - kv))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, dk, dv), F32), (q, k, v, g, beta, start))
    o = rmsnorm(o, p["out_norm_scale"], dm.eps) * jax.nn.silu(z.reshape(S, Hv, dv))
    return ops.mm(o.reshape(S, Hv * dv), p["w_o"])


def _partial_rope(x, pos, dm: Dims):
    r = dm.rotary_dim
    return jnp.concatenate([base.rope(x[..., :r], pos, dm.rope_theta), x[..., r:]], -1)


def gated_attention(ops, p, x, pos, seg, dm: Dims):
    S = x.shape[0]
    H, K, dh = dm.n_heads, dm.n_kv_heads, dm.head_dim
    q = rmsnorm(ops.mm(x, p["wq"]).reshape(S, H, dh), p["q_norm_scale"], dm.eps)
    k = rmsnorm(ops.mm(x, p["wk"]).reshape(S, K, dh), p["k_norm_scale"], dm.eps)
    v = ops.mm(x, p["wv"]).reshape(S, K, dh)
    q, k = _partial_rope(q, pos, dm), _partial_rope(k, pos, dm)
    # query head h reads kv head h // (H // K)
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    o = base._attend(ops, q, k, v, seg, 1.0 / np.sqrt(dh)).reshape(S, H * dh)
    return ops.mm(o * jax.nn.sigmoid(ops.mm(x, p["wq_gate"])), p["wo"])


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe(ops, p, i, x, dm: Dims, w_router=None):
    """The held experts' part of MoE layer ``i`` of the stacked params ``p``
    (every token through each, weighted by its combine weight, zero off its
    top-k), plus the gated shared expert."""
    if p["w_gate"].shape[1] != dm.n_held:
        raise ValueError(f"the weights hold {p['w_gate'].shape[1]} experts a layer; "
                         f"the configuration says {dm.n_held}")
    if w_router is None:
        w_router = p["w_router"][i]
    cw, top_i = base.route(ops, w_router, x, dm)
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
    sh = jax.tree.map(lambda a: a[i], p["shared"])
    y = y + jax.nn.sigmoid(ops.mm(x, sh["w_expert_gate"])) * swiglu(ops, sh, x)
    return y, top_i


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def layer_stack(weights, i: int, dm: Dims):
    """(stack, index, is MoE) of layer ``i``: the layers' norms and MoE
    with the stack of the layer's mixer kind (its key says which)."""
    mixer = "full_attn" if is_full(i, dm) else "linear_attn"
    return {**weights["blocks"], mixer: weights[mixer]}, jnp.int32(i), True


def pre_mlp(ops, stack, i, x, pos, seg, dm: Dims):
    """Residual after layer ``i``'s mixer, and the input of its MoE."""
    h = rmsnorm(x, stack["norm1"]["scale"][i], dm.eps)
    if "full_attn" in stack:
        a = jax.tree.map(lambda w: w[i // dm.full_every], stack["full_attn"])
        x = x + gated_attention(ops, a, h, pos, seg, dm)
    else:
        a = jax.tree.map(lambda w: w[i - (i + 1) // dm.full_every], stack["linear_attn"])
        x = x + gated_delta(ops, a, h, pos, seg, dm)
    return x, rmsnorm(x, stack["norm2"]["scale"][i], dm.eps)


@functools.partial(jax.jit, static_argnames=("dm", "mode", "is_moe"))
def layer(stack, i, x, pos, seg, *, dm: Dims, mode: str, is_moe: bool):
    """Layer ``i`` on one row: x (S, d) float32 -> (x, top-k indices)."""
    ops = Ops(mode)
    x, h = pre_mlp(ops, stack, i, x, pos, seg, dm)
    y, top_i = moe(ops, stack["moe"], i, h, dm)
    return x + y, top_i


@functools.partial(jax.jit, static_argnames=("dm",))
def pre_router(stack, i, x, pos, seg, *, dm: Dims):
    """Residual after layer ``i``'s mixer and its router's input, in
    bfloat16 arithmetic (the routing calibration's forward)."""
    return pre_mlp(Ops("bf16"), stack, i, x, pos, seg, dm)


@functools.partial(jax.jit, static_argnames=("dm",))
def moe_out(stack, i, w_router, x, u, *, dm: Dims):
    """The rest of layer ``i`` in bfloat16 with router ``w_router``:
    (residual out, top-k indices over the router's experts)."""
    y, top_i = moe(Ops("bf16"), stack["moe"], i, u, dm, w_router=w_router)
    return x + y, top_i


def hidden_states(weights, dm: Dims, rows, mode: str):
    """Final hidden states (before the final norm) of each packed row."""
    out = []
    for toks, pos, seg in rows:
        x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0).astype(F32)
        pos_j, seg_j = jnp.asarray(pos), jnp.asarray(seg)
        for i in range(dm.n_layers):
            stack, j, _ = layer_stack(weights, i, dm)
            x, _ = layer(stack, j, x, pos_j, seg_j, dm=dm, mode=mode, is_moe=True)
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
