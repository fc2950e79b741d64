"""Operation counts from shapes agree with the weight tree's sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import serve, work
from benchmark.reference import moe_transformer as ref
from benchmark.run import load_json
from benchmark.tests import tiny


def _size(tree):
    """Elements of the matrices of a stacked tree (norm scales are not
    multiplied by)."""
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree) if a.ndim >= 3)


@pytest.mark.parametrize("name", ["qwen3-30b-a3b.8L", "deepseek-v2-236b.2L"])
def test_active_params_match_program_tree(name):
    """The counts from published sizes agree with the program's parameter
    tree at full width (abstract: nothing is allocated)."""
    from repro.models import LM

    cfg = load_json(tiny.REPO / "benchmark" / "configs" / f"{name}.json")
    dm = ref.dims_from_config(cfg)
    lay = LM(serve.build_arch(cfg), dtype=jnp.bfloat16).abstract_params()
    attn = _size(lay["blocks"]["attn"]) // dm.n_moe_layers
    assert attn == work.attn_params(dm)
    moe = lay["blocks"]["moe"]
    expert = _size({k: moe[k] for k in ("w_gate", "w_up", "w_down")})
    assert expert == dm.n_moe_layers * dm.n_experts * work.expert_params(dm)


def test_expert_needs():
    dm = ref.dims_from_config(tiny.TINY_GQA)
    counts = np.zeros((2, 8), int)
    counts[0, :3] = [5, 1, 0]
    counts[1, 7] = 2
    flops, nbytes = work.expert_needs(dm, counts)
    assert flops == 2 * 3 * 64 * 32 * 8
    assert nbytes == 2 * (3 * 3 * 64 * 32 + 2 * 64 * 8)


def test_model_flops_counts_prefill_and_decode():
    dm = ref.dims_from_config(tiny.TINY_GQA)
    one = work.model_flops(dm, [10], [])
    assert one == float(work.token_flops(dm, 10, True))
    p = work.model_flops(dm, [], [4])
    assert p == float(sum(work.token_flops(dm, i, False) for i in range(1, 5))) + 2 * 64 * 256
