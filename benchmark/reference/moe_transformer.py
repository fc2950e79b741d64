"""Plain reference of a decoder-only MoE transformer, from its config file.

Covers both configurations of the benchmark: GQA attention (Qwen3-MoE)
or multi-head latent attention (DeepSeek-V2), an optional dense lead
layer, routed experts with softmax top-k routing, shared experts, the
final RMSNorm and the LM head.  It reads its sizes from the published
config keys of the configuration file and its weights from the tree the
benchmark made, and imports nothing of the program under test.

A configuration file names its reference (``"reference":
"moe_transformer"``) and the harness loads ``benchmark/reference/<name>.py``
by that name.  A reference module provides ``dims_from_config`` (sizes
from the published keys), ``served_gaps`` (the comparison that decides
``correct``) and, for routing shaping, ``layer_stack``, ``layer``,
``pre_router`` and ``moe_out``.

Everything is written out plainly: causal attention over whole rows
(in query chunks, so that the score matrix fits), every token through
every expert with its routing weight (zero off its top-k), no cache and
no capacity.  Several requests share one row of ``max_seq`` positions,
each with its own positions from 0 and attention only within itself.

``Ops`` fixes the arithmetic: ``"f32"`` is float32 at ``highest`` matmul
precision (the reference that decides ``correct``), ``"bf16"`` rounds
matmul inputs to bfloat16 (the cheap forward the routing calibration
uses), and ``"fp8"`` rounds them to float8 e4m3 with a scale per row of
activations and per column of weights (the control, one precision below
the configuration's bfloat16).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn
Q_CHUNK = 512  # query rows per attention block


@dataclass(frozen=True)
class Dims:
    d: int
    vocab: int
    n_layers: int
    n_dense_lead: int
    d_ff_dense: int
    attn: str  # "gqa" | "mla"
    n_heads: int
    n_kv_heads: int
    head_dim: int
    q_lora: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_head: int
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int
    norm_topk: bool
    routed_scale: float
    rope_theta: float
    eps: float

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_lead


def dims_from_config(cfg: Dict) -> Dims:
    """Sizes from the published config keys of a configuration file."""
    mla = "kv_lora_rank" in cfg
    n_experts = cfg.get("num_experts", cfg.get("n_routed_experts"))
    H = cfg["num_attention_heads"]
    return Dims(
        d=cfg["hidden_size"],
        vocab=cfg["vocab_size"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_lead=cfg.get("first_k_dense_replace", 0),
        d_ff_dense=cfg["intermediate_size"],
        attn="mla" if mla else "gqa",
        n_heads=H,
        n_kv_heads=cfg.get("num_key_value_heads", H),
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // H,
        q_lora=cfg.get("q_lora_rank") or 0,
        kv_lora=cfg.get("kv_lora_rank", 0),
        qk_nope=cfg.get("qk_nope_head_dim", 0),
        qk_rope=cfg.get("qk_rope_head_dim", 0),
        v_head=cfg.get("v_head_dim", 0),
        n_experts=n_experts,
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg.get("n_shared_experts") or 0,
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
    )


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------


def _fp8(a, axis):
    """Round ``a`` to float8 e4m3 with one scale per slice along ``axis``
    (None: one scale for the whole tensor); returns (bf16 values, scale)."""
    a = a.astype(F32)
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return q, scale


class Ops:
    """Matmul arithmetic of one precision mode (see the module docstring)."""

    def __init__(self, mode: str):
        if mode not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown precision mode {mode!r}")
        self.mode = mode

    def mm(self, x, w):
        """x (..., K) @ w (K, N): a linear layer."""
        if self.mode == "f32":
            return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)
        if self.mode == "bf16":
            return jnp.matmul(
                x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                preferred_element_type=F32,
            )
        qx, sx = _fp8(x, -1)
        qw, sw = _fp8(w, -2)
        return jnp.matmul(qx, qw, preferred_element_type=F32) * sx * sw

    def ein(self, spec, a, b):
        """An einsum whose operands are both activations or a weight stack."""
        if self.mode == "f32":
            return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)
        if self.mode == "bf16":
            return jnp.einsum(
                spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=F32,
            )
        qa, sa = _fp8(a, None)
        qb, sb = _fp8(b, None)
        return jnp.einsum(spec, qa, qb, preferred_element_type=F32) * sa * sb


# ---------------------------------------------------------------------------
# Layers (one row of S positions holding one or more requests)
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, pos, theta):
    """Rotate-half rope; x (S, heads, D), pos (S,)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(ops, q, k, v, seg, scale):
    """Causal attention within segments. q (S, H, Dq), k (S, H|1, Dq) or
    a pair for MLA, v (S, H, Dv); heads of k/v broadcast to q's."""
    S = q.shape[0]
    idx = jnp.arange(S)
    outs = []
    for s0 in range(0, S, Q_CHUNK):
        qs = q[s0:s0 + Q_CHUNK]
        n = qs.shape[0]
        s = ops.ein("qhd,thd->hqt", qs, k) * scale
        rows = idx[s0:s0 + n]
        mask = (rows[:, None] >= idx[None, :]) & (seg[s0:s0 + n, None] == seg[None, :])
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(ops.ein("hqt,thd->qhd", p, v))
    return jnp.concatenate(outs, 0)


def gqa(ops, p, x, pos, seg, dm: Dims):
    S = x.shape[0]
    H, K, dh = dm.n_heads, dm.n_kv_heads, dm.head_dim
    q = rope(ops.mm(x, p["wq"]).reshape(S, H, dh), pos, dm.rope_theta)
    k = rope(ops.mm(x, p["wk"]).reshape(S, K, dh), pos, dm.rope_theta)
    v = ops.mm(x, p["wv"]).reshape(S, K, dh)
    # query head h reads kv head h // (H // K)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    o = _attend(ops, q, k, v, seg, 1.0 / np.sqrt(dh))
    return ops.mm(o.reshape(S, H * dh), p["wo"])


def mla(ops, p, x, pos, seg, dm: Dims):
    S = x.shape[0]
    H, nope, r = dm.n_heads, dm.qk_nope, dm.qk_rope
    cq = rmsnorm(ops.mm(x, p["w_dq"]), p["q_norm_scale"], dm.eps)
    q = ops.mm(cq, p["w_uq"]).reshape(S, H, nope + r)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, dm.rope_theta)], -1)
    ckv = rmsnorm(ops.mm(x, p["w_dkv"]), p["kv_norm_scale"], dm.eps)
    k_pe = rope(ops.mm(x, p["w_kr"])[:, None, :], pos, dm.rope_theta)
    k_nope = ops.mm(ckv, p["w_uk"]).reshape(S, H, nope)
    v = ops.mm(ckv, p["w_uv"]).reshape(S, H, dm.v_head)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, r))], -1)
    o = _attend(ops, q, k, v, seg, 1.0 / np.sqrt(nope + r))
    return ops.mm(o.reshape(S, H * dm.v_head), p["wo"])


def swiglu(ops, p, x):
    return ops.mm(jax.nn.silu(ops.mm(x, p["w_gate"])) * ops.mm(x, p["w_up"]), p["w_down"])


def route(ops, w_router, x, dm: Dims):
    """Softmax over all experts, top-k; returns dense (S, E) combine weights
    and the top-k indices."""
    probs = jax.nn.softmax(ops.mm(x, w_router), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, dm.top_k)
    if dm.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * dm.routed_scale
    S = x.shape[0]
    dense = jnp.zeros((S, dm.n_experts), F32).at[jnp.arange(S)[:, None], top_i].set(top_p)
    return dense, top_i


def _expert_block(n_experts: int) -> int:
    return max(b for b in range(1, min(16, n_experts) + 1) if n_experts % b == 0)


def moe(ops, p, i, x, dm: Dims, w_router=None):
    """Routed experts of layer ``i`` of the stacked MoE params ``p`` (every
    token through every expert, weighted by its combine weight, zero off
    its top-k) plus shared experts.  Expert weights are sliced a block of
    experts at a time, so no layer's whole expert stack is copied."""
    if w_router is None:
        w_router = p["w_router"][i]
    cw, top_i = route(ops, w_router, x, dm)
    E, eb = dm.n_experts, _expert_block(dm.n_experts)
    nb = E // eb

    def sl(w, b):
        return jax.lax.dynamic_slice(
            w, (i, b * eb, 0, 0), (1, eb) + w.shape[2:])[0]

    def body(y, b):
        wg, wu, wd = sl(p["w_gate"], b), sl(p["w_up"], b), sl(p["w_down"], b)
        c = jax.lax.dynamic_slice_in_dim(cw, b * eb, eb, axis=1)
        h = jax.nn.silu(ops.ein("sd,edf->sef", x, wg)) * ops.ein("sd,edf->sef", x, wu)
        h = h * c[:, :, None]
        return y + ops.ein("sef,efd->sd", h, wd), None

    y, _ = jax.lax.scan(body, jnp.zeros(x.shape, F32), jnp.arange(nb))
    if dm.n_shared:
        y = y + swiglu(ops, jax.tree.map(lambda a: a[i], p["shared"]), x)
    return y, top_i


def attention(ops, p, x, pos, seg, dm: Dims):
    return (mla if dm.attn == "mla" else gqa)(ops, p, x, pos, seg, dm)


def pre_mlp(ops, stack, i, x, pos, seg, dm: Dims):
    """Residual after layer ``i``'s attention, and the input of its MLP."""
    a = jax.tree.map(lambda w: w[i], stack["attn"])
    x = x + attention(ops, a, rmsnorm(x, stack["norm1"]["scale"][i], dm.eps), pos, seg, dm)
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
    (residual out, top-k indices)."""
    y, top_i = moe(Ops("bf16"), stack["moe"], i, u, dm, w_router=w_router)
    return x + y, top_i


@functools.partial(jax.jit, static_argnames=("dm", "mode"))
def head(final_scale, w_out, h, tokens, *, dm: Dims, mode: str):
    """Logits of rows ``h`` (N, d): their max, argmax and the logit of
    ``tokens`` (N,)."""
    logits = Ops(mode).mm(rmsnorm(h, final_scale, dm.eps), w_out)
    at = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return logits.max(-1), logits.argmax(-1).astype(jnp.int32), at


def layer_stack(weights, i: int, dm: Dims):
    """(stacked block tree, index in it, is MoE) of layer ``i``; the dense
    lead layers come first."""
    if i < dm.n_dense_lead:
        return weights["prefix_blocks"], jnp.int32(i), False
    return weights["blocks"], jnp.int32(i - dm.n_dense_lead), True


def pack_rows(seqs, row_len: int):
    """Greedy first-fit of token sequences into rows of ``row_len``:
    returns rows of (tokens, positions, segment ids) and, per sequence,
    (row, offset)."""
    rows, place = [], []
    fill = []
    for s in seqs:
        n = len(s)
        if n > row_len:
            raise ValueError(f"sequence of {n} tokens exceeds a row of {row_len}")
        r = next((i for i, f in enumerate(fill) if f + n <= row_len), None)
        if r is None:
            rows.append((np.zeros(row_len, np.int32), np.zeros(row_len, np.int32),
                         np.full(row_len, -1, np.int32)))
            fill.append(0)
            r = len(rows) - 1
        off = fill[r]
        toks, pos, seg = rows[r]
        toks[off:off + n] = s
        pos[off:off + n] = np.arange(n)
        seg[off:off + n] = len(place)
        place.append((r, off))
        fill[r] += n
    # padding positions form segments of their own (never attended by a real one)
    for toks, pos, seg in rows:
        pad = seg < 0
        seg[pad] = -1 - np.arange(int(pad.sum()))
    return rows, place


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
    reference's best at the position that produced it.

    With ``control`` set (a precision mode), the same positions are also
    run in that precision, and the gap is read for the token it puts
    first.  Returns (gaps, control_gaps or None, argmax agreement)."""
    seqs = [list(p) + list(g[:-1]) for p, g in requests]
    rows, place = pack_rows(seqs, row_len)
    ref_h = hidden_states(weights, dm, rows, "f32")
    ctl_h = hidden_states(weights, dm, rows, control) if control else None
    # (row, position) of every served token and the token itself
    sel_r, sel_p, toks = [], [], []
    for (prompt, gen), (r, off) in zip(requests, place):
        P = len(prompt)
        for i, t in enumerate(gen):
            sel_r.append(r)
            sel_p.append(off + P - 1 + i)
            toks.append(int(t))
    sel_r, sel_p, toks = map(np.asarray, (sel_r, sel_p, toks))
    scale, w_out = weights["final_norm"]["scale"], weights["w_out"]

    def gather(hs):
        return jnp.stack(hs)[jnp.asarray(sel_r), jnp.asarray(sel_p)]

    h_ref = gather(ref_h)
    h_ctl = gather(ctl_h) if ctl_h is not None else None
    gaps, cgaps, agree = [], [], []
    for c0 in range(0, len(toks), chunk):
        sl = slice(c0, c0 + chunk)
        n = len(toks[sl])
        pad = chunk - n
        hr = jnp.pad(h_ref[sl], ((0, pad), (0, 0)))
        tk = jnp.asarray(np.pad(toks[sl], (0, pad)))
        mx, am, at = head(scale, w_out, hr, tk, dm=dm, mode="f32")
        gaps.append(np.asarray(mx - at)[:n])
        agree.append(np.asarray(am)[:n] == toks[sl])
        if h_ctl is not None:
            hc = jnp.pad(h_ctl[sl], ((0, pad), (0, 0)))
            _, c_am, _ = head(scale, w_out, hc, tk, dm=dm, mode=control)
            _, _, c_at = head(scale, w_out, hr, c_am, dm=dm, mode="f32")
            cgaps.append(np.asarray(mx - c_at)[:n])
    gaps = np.concatenate(gaps)
    cgaps = np.concatenate(cgaps) if cgaps else None
    return gaps, cgaps, float(np.concatenate(agree).mean())
