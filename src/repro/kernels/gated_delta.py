"""Gated DeltaNet decode recurrence (Qwen3-Next's linear attention), one
token per sequence, updating the layer-stacked recurrent state in place.

For value head ``h`` of row ``b`` with state ``S`` (``(d_k, d_v)``, float32):

    S <- decay * S
    S <- S + k (beta (v - S^T k))^T
    o  = S^T q

The state of every linear layer is one ``(L, B, H, d_k, d_v)`` array; the
kernel takes it whole, with the layer in its scalar-prefetch operand, and
returns it through ``input_output_aliases``: each grid step reads one
block of heads of one row once and writes it back once, and nothing
outside the kernel slices, copies or writes back a layer's state.

The arithmetic is on the VPU in float32 (elementwise products and sums),
with the state held ``(d_k, d_v)`` as the published code holds it: k and q
enter as rows over ``d_k`` and are turned into columns by a masked lane
sum (exact: one nonzero term), so no value leaves float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _column(row: jax.Array, eye: jax.Array) -> jax.Array:
    """A ``(1, n)`` row as an ``(n, 1)`` column."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _gated_delta_kernel(layer_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref,
                        s_ref, o_ref, s_out_ref, *, hb: int):
    dk = s_ref.shape[-2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    for h in range(hb):
        s = s_ref[h] * decay_ref[h:h + 1, :]  # (dk, dv), decay along lanes
        k_col = _column(k_ref[h:h + 1, :], eye)  # (dk, 1)
        kv = jnp.sum(s * k_col, axis=0, keepdims=True)  # (1, dv): S^T k
        delta = beta_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - kv)
        s = s + k_col * delta
        s_out_ref[h] = s
        o_ref[h:h + 1, :] = jnp.sum(s * _column(q_ref[h:h + 1, :], eye), axis=0,
                                    keepdims=True)


def gated_delta_decode(
    state: jax.Array,  # (L, B, H, dk, dv) float32, every linear layer
    layer: jax.Array,  # () int32: the layer this call updates
    q: jax.Array,  # (B, H, dk) float32, l2-normed and scaled
    k: jax.Array,  # (B, H, dk) float32, l2-normed
    v: jax.Array,  # (B, H, dv) float32
    decay: jax.Array,  # (B, H, dv) float32: exp(g), the same along dv
    beta: jax.Array,  # (B, H, dv) float32: sigmoid(b), the same along dv
    *,
    hb: int = 16,
    interpret: bool = False,
):
    """Returns ``(o (B, H, dv) float32, state)`` with layer ``layer`` of
    ``state`` advanced one token; ``state`` is aliased to the output."""
    L, B, H, dk, dv = state.shape
    hb = min(hb, H)
    assert H % hb == 0, (H, hb)
    row = lambda b, j, l: (b, j, 0)  # noqa: E731
    vec = lambda n: pl.BlockSpec((None, hb, n), row)  # noqa: E731
    st = pl.BlockSpec((None, None, hb, dk, dv), lambda b, j, l: (l[0], b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // hb),
        in_specs=[vec(dk), vec(dk), vec(dv), vec(dv), vec(dv), st],
        out_specs=[vec(dv), st],
    )
    return pl.pallas_call(
        functools.partial(_gated_delta_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after the scalar-prefetch layer) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q, k, v, decay, beta, state)
