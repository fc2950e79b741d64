"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fused_swiglu_gmm_ref(
    buf: jax.Array,  # (G, C, K) capacity-layout dispatch buffer
    wg: jax.Array,  # (E, K, F)
    wu: jax.Array,  # (E, K, F)
    wd: jax.Array,  # (E, F, N)
    group_sizes: jax.Array,  # (G,) real rows per group
    rhs_of_group: jax.Array | None = None,  # (G,) weight row per group
) -> jax.Array:
    """Dense SwiGLU over the capacity slab; padding rows -> 0."""
    if rhs_of_group is not None:
        wg, wu, wd = wg[rhs_of_group], wu[rhs_of_group], wd[rhs_of_group]
    x = buf.astype(jnp.float32)
    gate = jnp.einsum("gck,gkf->gcf", x, wg.astype(jnp.float32))
    up = jnp.einsum("gck,gkf->gcf", x, wu.astype(jnp.float32))
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("gcf,gfn->gcn", h, wd.astype(jnp.float32))
    live = (
        jnp.arange(buf.shape[1])[None, :] < group_sizes[:, None]
    )
    return (y * live[..., None]).astype(buf.dtype)


def fused_swiglu_gemv_ref(
    tokens: jax.Array,  # (S, K)
    wg: jax.Array,  # (E, K, F)
    wu: jax.Array,  # (E, K, F)
    wd: jax.Array,  # (E, F, N)
    expert_ids: jax.Array,  # (S,)
    valid: jax.Array,  # (S,)
) -> jax.Array:
    x = tokens.astype(jnp.float32)
    gate = jnp.einsum("sk,skf->sf", x, wg[expert_ids].astype(jnp.float32))
    up = jnp.einsum("sk,skf->sf", x, wu[expert_ids].astype(jnp.float32))
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("sf,sfn->sn", h, wd[expert_ids].astype(jnp.float32))
    return (y * (valid > 0)[:, None]).astype(tokens.dtype)


def decode_attention_ref(
    q: jax.Array,  # (B, H, dh)
    cache_k: jax.Array,  # (B, Kv, T, dh) head-major
    cache_v: jax.Array,  # (B, Kv, T, dh)
    lengths: jax.Array,  # (B,)
) -> jax.Array:
    B, H, dh = q.shape
    Kv, T = cache_k.shape[1], cache_k.shape[2]
    G = H // Kv
    qf = q.reshape(B, Kv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qf, cache_k.astype(jnp.float32)) / (dh**0.5)
    mask = jnp.arange(T)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, cache_v.astype(jnp.float32))
    return o.reshape(B, H, dh).astype(q.dtype)


def decode_attention_paged_ref(
    q: jax.Array,  # (B, H, dh)
    pool_k: jax.Array,  # (n_pool, Kv, page, dh) head-major block pool
    pool_v: jax.Array,  # (n_pool, Kv, page, dh)
    block_tables: jax.Array,  # (B, max_blocks) int32 logical -> physical
    lengths: jax.Array,  # (B,)
) -> jax.Array:
    """Gather the slot's pool blocks into a dense cache and fall back to
    :func:`decode_attention_ref` — the semantic definition of the paged
    layout (dead table cells point at the trash block and are masked by
    ``lengths``)."""
    return decode_attention_ref(
        q, gather_pages(pool_k, block_tables), gather_pages(pool_v, block_tables),
        lengths,
    )


def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """``(n_pool, Kv, page, dh)`` pool + ``(B, nb)`` table -> the dense
    head-major ``(B, Kv, nb * page, dh)`` cache the table describes."""
    B, nb = block_tables.shape
    _, Kv, page, dh = pool.shape
    g = pool[block_tables]  # (B, nb, Kv, page, dh)
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Kv, nb * page, dh)


def gated_delta_decode_ref(
    state: jax.Array,  # (L, B, H, dk, dv) float32
    layer: jax.Array,  # () int32
    q: jax.Array,  # (B, H, dk)
    k: jax.Array,  # (B, H, dk)
    v: jax.Array,  # (B, H, dv)
    decay: jax.Array,  # (B, H): exp(g)
    beta: jax.Array,  # (B, H)
):
    """One token of the gated delta rule on layer ``layer`` of the stacked
    state, as HF ``torch_recurrent_gated_delta_rule`` steps it: returns
    ``(o (B, H, dv) float32, state with that layer advanced)``."""
    f32 = jnp.float32
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False).astype(f32)
    s = s * decay.astype(f32)[..., None, None]
    kf, qf = k.astype(f32), q.astype(f32)
    kv = jnp.einsum("bhkv,bhk->bhv", s, kf, precision="highest")
    delta = beta.astype(f32)[..., None] * (v.astype(f32) - kv)
    s = s + kf[..., :, None] * delta[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, qf, precision="highest")
    return o, jax.lax.dynamic_update_index_in_dim(state, s.astype(state.dtype), layer, 0)
