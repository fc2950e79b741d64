"""Operations and bytes of Qwen3-Next's hybrid step, from shapes and counts.

``work`` counts GQA in every layer; here a configuration whose layers
alternate Gated DeltaNet linear attention and gated softmax attention
(``dm`` from ``reference/qwen3_next.dims_from_config``) is counted layer
kind by layer kind.  Model FLOPs are 2 per multiply-add of every weight a
token uses, attention over the full layers' KV depth, and ~6 per element
of each value head's ``d_k x d_v`` state for the recurrence (decay,
``S^T k``, the rank-one update, ``S^T q``).  The decode recurrence's
bytes are what it must move: each live row's float32 state read and
written once per linear layer, and its q, k, v, gates and output row.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np

from benchmark import work

F32 = 4


def n_full(dm) -> int:
    return dm.n_layers // dm.full_every


def n_linear(dm) -> int:
    return dm.n_layers - n_full(dm)


def state_elems(dm) -> int:
    """Elements of one row's recurrent state in one linear layer."""
    return dm.lin_v_heads * dm.lin_k_dim * dm.lin_v_dim


def conv_dim(dm) -> int:
    return 2 * dm.lin_k_heads * dm.lin_k_dim + dm.lin_v_heads * dm.lin_v_dim


def linear_params(dm) -> int:
    """Weights a token multiplies by in one Gated DeltaNet layer:
    ``W_qkvz``, ``W_ba``, the conv taps and ``W_o``."""
    value = dm.lin_v_heads * dm.lin_v_dim
    return (dm.d * (conv_dim(dm) + value) + dm.d * 2 * dm.lin_v_heads
            + dm.conv_width * conv_dim(dm) + value * dm.d)


def full_params(dm) -> int:
    """Weights of one gated attention layer: ``q_proj`` doubled by the
    gate, k, v and o."""
    return 3 * dm.d * dm.n_heads * dm.head_dim + 2 * dm.d * dm.n_kv_heads * dm.head_dim


def moe_dense_params(dm) -> int:
    """Weights of a MoE layer every token uses: router, shared expert and
    its gate."""
    return dm.d * dm.n_experts + dm.n_shared * work.expert_params(dm) + dm.d


def recurrence_flops(dm) -> float:
    return 6.0 * state_elems(dm)


def token_flops(dm, kv_len, head: bool, routed_share: float) -> np.ndarray:
    """Model FLOPs of one token (or an array of them) at full-attention KV
    depth ``kv_len``, with the routed experts counted at ``routed_share``
    of its top-k assignments (the share the held experts take)."""
    kv = np.asarray(kv_len, np.float64)
    f = 2.0 * (n_linear(dm) * linear_params(dm) + n_full(dm) * full_params(dm)
               + dm.n_layers * moe_dense_params(dm))
    f += n_linear(dm) * recurrence_flops(dm)
    f += dm.n_layers * routed_share * dm.top_k * 2.0 * work.expert_params(dm)
    f = f + n_full(dm) * 2.0 * 2 * dm.n_heads * dm.head_dim * kv
    if head:
        f = f + 2.0 * dm.d * dm.vocab
    return f


def model_flops(dm, decode_kv: Iterable[int], prefill_lens: Iterable[int],
                routed_share: float) -> float:
    """Decode tokens at their KV depths (with the LM head) plus whole
    prompts (the LM head once, for the last position)."""
    total = float(np.sum(token_flops(dm, np.asarray(list(decode_kv), np.float64), True,
                                     routed_share)))
    for P in prefill_lens:
        total += float(np.sum(token_flops(dm, np.arange(1, P + 1), False, routed_share)))
        total += 2.0 * dm.d * dm.vocab
    return total


def gated_delta_needs(dm, rows: int):
    """(flops, bytes) the decode recurrence needs for ``rows`` live rows
    over every linear layer: the state read and written once in float32,
    and the q, k, v, g, beta and output rows in float32."""
    vectors = (2 * dm.lin_k_heads * dm.lin_k_dim + 2 * dm.lin_v_heads * dm.lin_v_dim
               + 2 * dm.lin_v_heads)
    per_row = 2.0 * F32 * state_elems(dm) + F32 * vectors
    n = float(rows) * n_linear(dm)
    return n * recurrence_flops(dm), n * per_row
