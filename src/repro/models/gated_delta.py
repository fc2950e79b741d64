"""Gated DeltaNet linear attention (Qwen3-Next's ``linear_attention``
layers): projections, causal depthwise conv, the gated delta rule and the
gated output norm.

For a token ``x`` and value head ``h`` (key head ``h // (Hv // Hk)``)::

    [q, k, v, z] = x W_qkvz        [b, a] = x W_ba
    q, k, v = SiLU(conv(q, k, v))  q = l2norm(q) / sqrt(d_k), k = l2norm(k)
    g = -exp(A_log) softplus(a + dt_bias)        beta = sigmoid(b)
    S <- exp(g) S ;  S <- S + k (beta (v - S^T k))^T ;  o = S^T q
    out = (RMSNorm(o) * w * SiLU(z)) W_o

as HF ``modeling_qwen3_next`` computes it, with the recurrence in float32.
``W_qkvz`` and ``W_ba`` lay their columns out flat (all q, all k, all v,
all z), where the published checkpoint groups them per key head: a fixed
permutation of columns.  The conv's taps are ``conv_w`` rows, oldest
first; its state is the last ``conv_width - 1`` inputs, oldest first.

Prefill runs the chunked (WY) form of the recurrence in ``jnp``
(:func:`chunked_gated_delta`); decode runs one token through the Pallas
kernel ``gated_delta_decode`` on a TPU (the whole layer-stacked state
updated in place) and through its oracle elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import LinearAttnConfig
from .layers import _he

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64  # tokens a chunk of the prefill recurrence
EPS = 1e-6


def _decode_mode() -> str:
    """``"kernel"``: the Pallas recurrence on a TPU; ``"xla"``: its oracle
    elsewhere (interpret-mode Pallas is too slow to serve from)."""
    return "kernel" if jax.default_backend() == "tpu" else "xla"


def init_gated_delta(key, cfg: LinearAttnConfig, d_model: int,
                     dtype=jnp.bfloat16) -> dict:
    """Every leaf is a matrix (He-scaled over ``shape[-2]``) or a norm
    ``*scale``: ``A_log`` and ``dt_bias`` are kept ``(1, Hv)``."""
    Hk, Hv, dk, dv = cfg.n_k_heads, cfg.n_v_heads, cfg.d_k, cfg.d_v
    ks = jax.random.split(key, 6)
    return {
        "w_qkvz": _he(ks[0], (d_model, 2 * Hk * dk + 2 * Hv * dv), 1.0, dtype),
        "w_ba": _he(ks[1], (d_model, 2 * Hv), 1.0, dtype),
        "conv_w": _he(ks[2], (cfg.conv_width, cfg.conv_dim), 1.0, dtype),
        "A_log": _he(ks[3], (1, Hv), 1.0, F32),
        "dt_bias": _he(ks[4], (1, Hv), 1.0, F32),
        "out_norm_scale": jnp.ones((dv,), F32),
        "w_o": _he(ks[5], (Hv * dv, d_model), 1.0, dtype),
    }


def _l2norm(u):
    return u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + EPS)


def _inputs(p, x, conv_out, cfg: LinearAttnConfig):
    """From the layer input ``x`` (B, S, d) and the conv's SiLU output
    (B, S, conv_dim): float32 q, k (B, S, Hv, dk) (each key head repeated
    over its value heads), v (B, S, Hv, dv), g, beta (B, S, Hv)."""
    Hk, Hv, dk, dv = cfg.n_k_heads, cfg.n_v_heads, cfg.d_k, cfg.d_v
    B, S = x.shape[:2]
    q = _l2norm(conv_out[..., :Hk * dk].reshape(B, S, Hk, dk)) * (dk ** -0.5)
    k = _l2norm(conv_out[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk))
    v = conv_out[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    ba = (x @ p["w_ba"]).astype(F32)
    b, a = ba[..., :Hv], ba[..., Hv:]
    g = -jnp.exp(p["A_log"][0]) * jax.nn.softplus(a + p["dt_bias"][0])
    r = Hv // Hk
    return (jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g,
            jax.nn.sigmoid(b))


def _project(p, x, cfg: LinearAttnConfig):
    """(pre-conv q, k, v channels (B, S, conv_dim), z (B, S, Hv*dv))."""
    qkvz = x @ p["w_qkvz"]
    return qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]


def _output(p, o, z, cfg: LinearAttnConfig, dtype):
    """Gated RMSNorm over each head's ``dv``, then ``W_o``."""
    B, S = z.shape[:2]
    o = o.astype(F32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + EPS)
    o = o * p["out_norm_scale"] * jax.nn.silu(
        z.reshape(B, S, cfg.n_v_heads, cfg.d_v).astype(F32))
    return o.reshape(B, S, -1).astype(dtype) @ p["w_o"]


def chunked_gated_delta(q, k, v, g, beta, state=None, chunk: int = CHUNK):
    """The gated delta rule over a whole sequence in chunks of ``chunk``
    tokens (HF ``torch_chunk_gated_delta_rule``): within a chunk the
    rule's products are solved as one lower-triangular system (the WY
    form), across chunks the state carries.  q, k (B, S, H, dk), v (B, S,
    H, dv), g, beta (B, S, H), all float32; q already scaled.  Returns
    ``(o (B, S, H, dv), final state (B, H, dk, dv))``."""
    B, S, H, dk = k.shape
    dv = v.shape[-1]
    pad = -S % chunk
    if pad:
        # padded tokens: k = v = 0 and g = 0 leave the state as it was
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (g, beta))
    n = (S + pad) // chunk

    def chunks(t):  # (B, S, H, ...) -> (n, B, H, chunk, ...)
        t = t.reshape((B, n, chunk, H) + t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    ein = lambda s, a, b: jnp.einsum(s, a, b, precision=HIGHEST)  # noqa: E731
    g = jnp.cumsum(g, axis=-1)  # (n, B, H, C)
    tril = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tril, jnp.exp(jnp.where(tril, g[..., :, None] - g[..., None, :], 0.0)),
                      0.0)
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    lower = jnp.where(tril & ~jnp.eye(chunk, dtype=bool),
                      ein("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
    # (I + lower)^-1 by a triangular solve: the forward substitution of
    # the published loop
    inv = jax.scipy.linalg.solve_triangular(
        jnp.eye(chunk, dtype=F32) + lower,
        jnp.broadcast_to(jnp.eye(chunk, dtype=F32), lower.shape), lower=True)
    u = ein("...ij,...jv->...iv", inv, v_beta)
    w = ein("...ij,...jk->...ik", inv, k_beta * jnp.exp(g)[..., None])
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(s, c):
        qc, kc, uc, wc, gc, dc = c
        attn = jnp.where(causal, ein("...ik,...jk->...ij", qc, kc) * dc, 0.0)
        v_new = uc - ein("...ik,...kv->...iv", wc, s)
        o = ein("...ik,...kv->...iv", qc * jnp.exp(gc)[..., None], s) + ein(
            "...ij,...jv->...iv", attn, v_new)
        last = gc[..., -1:]
        s = s * jnp.exp(last)[..., None] + ein(
            "...ik,...iv->...kv", kc * jnp.exp(last - gc)[..., None], v_new)
        return s, o

    s0 = jnp.zeros((B, H, dk, dv), F32) if state is None else state
    s, o = jax.lax.scan(step, s0, (q, k, u, w, g, decay))
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(B, n * chunk, H, dv)
    return o[:, :S], s


def gated_delta_prefill(p, x, cfg: LinearAttnConfig, chunk: int = CHUNK):
    """A whole prompt (B, S, d) -> ``(y, (state (B, Hv, dk, dv) float32,
    conv state (B, conv_width - 1, conv_dim)))``."""
    B, S, _ = x.shape
    w = cfg.conv_width
    with jax.named_scope("gated_delta/conv"):
        mixed, z = _project(p, x, cfg)
        xp = jnp.pad(mixed, ((0, 0), (w - 1, 0), (0, 0)))
        taps = p["conv_w"].astype(F32)
        conv = sum(xp[:, i:i + S].astype(F32) * taps[i] for i in range(w))
        q, k, v, g, beta = _inputs(p, x, jax.nn.silu(conv), cfg)
        conv_state = xp[:, S:]
    with jax.named_scope("gated_delta/recurrence"):
        o, state = chunked_gated_delta(q, k, v, g, beta, chunk=chunk)
    with jax.named_scope("gated_delta/out"):
        y = _output(p, o, z, cfg, x.dtype)
    return y, (state, conv_state)


def gated_delta_decode(p, x, state, conv_state, layer, cfg: LinearAttnConfig):
    """One token per row, x (B, 1, d), of linear layer ``layer`` of the
    whole layer-stacked states: ``state`` (L, B, Hv, dk, dv) float32 and
    ``conv_state`` (L, B, conv_width - 1, conv_dim).  Returns ``(y, (state,
    conv_state))`` with that layer's rows advanced."""
    with jax.named_scope("gated_delta/conv"):
        mixed, z = _project(p, x, cfg)
        prev = jax.lax.dynamic_index_in_dim(conv_state, layer, 0, keepdims=False)
        window = jnp.concatenate([prev, mixed.astype(prev.dtype)], axis=1)
        conv = jnp.einsum("bwc,wc->bc", window.astype(F32), p["conv_w"].astype(F32))
        conv_state = jax.lax.dynamic_update_index_in_dim(
            conv_state, window[:, 1:], layer, 0)
        q, k, v, g, beta = (t[:, 0] for t in _inputs(
            p, x, jax.nn.silu(conv)[:, None], cfg))
    with jax.named_scope("gated_delta/recurrence"):
        if _decode_mode() == "kernel":
            from repro.kernels import ops as kernel_ops

            o, state = kernel_ops.gated_delta_decode(
                state, layer, q, k, v, jnp.exp(g), beta)
        else:
            from repro.kernels.ref import gated_delta_decode_ref

            o, state = gated_delta_decode_ref(state, layer, q, k, v, jnp.exp(g), beta)
    with jax.named_scope("gated_delta/out"):
        y = _output(p, o[:, None], z, cfg, x.dtype)
    return y, (state, conv_state)
