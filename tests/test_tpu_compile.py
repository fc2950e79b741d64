"""Compile the main path's Pallas kernels, and one decode step, for a
described TPU v5e at qwen3-moe-30b-a3b widths.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (tiling, VMEM, memory).  The topology is described inside a
fixture, so only the worker that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.launch.serve import cut_depth
from repro.models import LM, attention, moe

ARCH = get_arch("qwen3-moe-30b-a3b")
E, D, F = ARCH.moe.n_experts, ARCH.d_model, ARCH.moe.d_expert
H, KV, DH = ARCH.attn.n_heads, ARCH.attn.n_kv_heads, ARCH.attn.d_head
SLOTS, MAX_SEQ, PAGE = 32, 2048, 64
# one DeepSeek-V2 chip's share: 20 held experts at published widths
DS = get_arch("deepseek-v2-236b")
DS_E, DS_D, DS_F = 20, DS.d_model, DS.moe.d_expert
BF, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip executable can be written to the persistent cache
    but not read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _kernel_case(name, sds):
    """(function, abstract args) for one kernel at qwen3 widths."""
    w_in, w_out = sds((E, D, F)), sds((E, F, D))
    toks, eids = sds((E, D)), sds((E,), I32)
    q, lens = sds((SLOTS, H, DH)), sds((SLOTS,), I32)
    cache = sds((SLOTS, KV, MAX_SEQ, DH))
    n_pool = SLOTS * MAX_SEQ // PAGE + 1
    pool = sds((n_pool, KV, PAGE, DH))
    ds_in, ds_out = sds((DS_E, DS_D, DS_F)), sds((DS_E, DS_F, DS_D))

    def gmm(b, g, u, d, s):
        return ops.swiglu_gmm_capacity(b, g, u, d, s, interpret=False)

    def gemv(t, g, u, d, e, v):
        return ops.swiglu_gemv(t, g, u, d, e, v, interpret=False)

    return {
        "swiglu_gmm_capacity": (
            lambda b, g, u, d, s: ops.swiglu_gmm_capacity(
                b, g, u, d, s, interpret=False
            ),
            (sds((E, 8, D)), w_in, w_in, w_out, sds((E,), I32)),
        ),
        "swiglu_gemv": (
            lambda t, g, u, d, e, v: ops.swiglu_gemv(
                t, g, u, d, e, v, interpret=False
            ),
            (toks, w_in, w_in, w_out, eids, eids),
        ),
        # the cells' own capacities: qwen3 at 48 slots, a 512-token
        # prefill (four m-tiles a group), the DeepSeek-V2 share's 20 held
        # experts at 128 slots
        "swiglu_gmm_capacity.qwen3_decode": (
            gmm, (sds((E, 48, D)), w_in, w_in, w_out, sds((E,), I32)),
        ),
        "swiglu_gmm_capacity.qwen3_prefill": (
            gmm, (sds((E, 512, D)), w_in, w_in, w_out, sds((E,), I32)),
        ),
        "swiglu_gmm_capacity.deepseek_share": (
            gmm, (sds((DS_E, 128, DS_D)), ds_in, ds_in, ds_out, sds((DS_E,), I32)),
        ),
        "swiglu_gemv.deepseek_share": (
            gemv, (sds((DS_E, DS_D)), ds_in, ds_in, ds_out, sds((DS_E,), I32),
                   sds((DS_E,), I32)),
        ),
        "decode_attention": (
            lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False),
            (q, cache, cache, lens),
        ),
        "decode_attention_split4": (
            lambda q, k, v, n: ops.decode_attention(
                q, k, v, n, n_splits=4, interpret=False
            ),
            (q, cache, cache, lens),
        ),
        "decode_attention_paged": (
            lambda q, k, v, t, n: ops.decode_attention_paged(
                q, k, v, t, n, interpret=False
            ),
            (q, pool, pool, sds((SLOTS, MAX_SEQ // PAGE), I32), lens),
        ),
    }[name]


@pytest.mark.parametrize(
    "name",
    [
        "swiglu_gmm_capacity",
        "swiglu_gemv",
        "swiglu_gmm_capacity.qwen3_decode",
        "swiglu_gmm_capacity.qwen3_prefill",
        "swiglu_gmm_capacity.deepseek_share",
        "swiglu_gemv.deepseek_share",
        "decode_attention",
        "decode_attention_split4",
        "decode_attention_paged",
    ],
)
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    def sds(shape, dtype=BF):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(name, sds)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if name.startswith("swiglu"):
        # the grid bound is the live count, and a call with none live
        # skips the kernel
        assert " conditional(" in text


def test_qwen3_decode_step_compiles_for_v5e(
    one_chip, no_compile_cache, monkeypatch
):
    """One full-width layer of the served decode step, steered onto the
    Pallas path the chip selects (this host would pick the XLA twins)."""
    monkeypatch.setattr(moe, "_dual_backend", lambda: "pallas")
    monkeypatch.setattr(attention, "_flash_decode_mode", lambda: "kernel")
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    lm = LM(cut_depth(ARCH, 1), dtype=BF)

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = placed(lm.abstract_params())
    cache = placed(jax.eval_shape(lambda: lm.init_cache(SLOTS, MAX_SEQ)))
    batch = placed({
        "tokens": jax.ShapeDtypeStruct((SLOTS, 1), I32),
        "position": jax.ShapeDtypeStruct((SLOTS,), I32),
    })
    compiled = (
        jax.jit(lm.decode_step, donate_argnums=(2,))
        .lower(params, batch, cache)
        .compile()
    )
    text = compiled.as_text()
    # head grouped GEMM, tail GEMV and decode attention all compiled in
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
    # under the names the benchmark's device-trace reduction reads
    # (`benchmark/readers.py` EXPERT_KERNELS), though each sits in a branch
    import re

    for kernel in ("swiglu_gmm_capacity", "swiglu_gemv", "decode_attention"):
        assert re.search(rf"%{kernel}\.\d+ = \S+ custom-call\(", text), kernel


def test_engine_decode_program_picks_ids_for_v5e(
    one_chip, no_compile_cache, monkeypatch
):
    """The engine's compiled decode function at qwen3's full width and the
    cell's 48 slots: the greedy argmax over all 151936 columns compiles for
    the chip beside the decode step, and the program returns the ``(48,)``
    int32 ids the engine copies to the host, beside the logits."""
    from repro.serving.engine import decode_program

    monkeypatch.setattr(moe, "_dual_backend", lambda: "pallas")
    monkeypatch.setattr(attention, "_flash_decode_mode", lambda: "kernel")
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    lm = LM(cut_depth(ARCH, 1), dtype=BF)
    slots = 48

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = placed(lm.abstract_params())
    cache = placed(jax.eval_shape(lambda: lm.init_cache(slots, MAX_SEQ)))
    batch = placed({
        "tokens": jax.ShapeDtypeStruct((slots, 1), I32),
        "position": jax.ShapeDtypeStruct((slots,), I32),
    })
    lowered = jax.jit(decode_program(lm), donate_argnums=(2,)).lower(
        params, batch, cache
    )
    compiled = lowered.compile()
    (ids, logits), _, _ = compiled.out_info
    assert (ids.shape, ids.dtype) == ((slots,), jnp.int32)
    assert logits.shape == (slots, 1, ARCH.vocab_size)
    assert lowered.as_text().startswith("module @jit_decode_step")
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') >= 3


def test_qwen3_decode_step_reads_expert_stacks_in_place(
    one_chip, no_compile_cache, monkeypatch
):
    """Two full-width layers of the served decode step: the expert kernels
    read the layer-stacked weights through a bitcast to ``(L*E, ...)``, so
    the compiled program makes no value of one layer's ``(E, d, f)`` /
    ``(E, f, d)`` stack and no copy of a whole stack."""
    import re

    monkeypatch.setattr(moe, "_dual_backend", lambda: "pallas")
    monkeypatch.setattr(attention, "_flash_decode_mode", lambda: "kernel")
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    L = 2
    lm = LM(cut_depth(ARCH, L), dtype=BF)
    assert lm.moe_layers_in_place() == L

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = placed(lm.abstract_params())
    cache = placed(jax.eval_shape(lambda: lm.init_cache(SLOTS, MAX_SEQ)))
    batch = placed({
        "tokens": jax.ShapeDtypeStruct((SLOTS, 1), I32),
        "position": jax.ShapeDtypeStruct((SLOTS,), I32),
    })
    text = (
        jax.jit(lm.decode_step, donate_argnums=(2,))
        .lower(params, batch, cache)
        .compile()
        .as_text()
    )
    layer = {f"bf16[{E},{D},{F}]", f"bf16[{E},{F},{D}]"}
    whole = {f"bf16[{L},{E},{D},{F}]", f"bf16[{L},{E},{F},{D}]",
             f"bf16[{L * E},{D},{F}]", f"bf16[{L * E},{F},{D}]"}
    ops_of = {}
    for m in re.finditer(r"= (bf16\[[\d,]+\])\S* ([\w\-]+)\(", text):
        ops_of.setdefault(m.group(1), set()).add(m.group(2))
    assert not layer & set(ops_of)
    moved = set().union(*(ops_of.get(s, set()) for s in whole))
    assert "bitcast" in moved
    assert moved <= {"parameter", "bitcast", "get-tuple-element", "tuple"}


def test_deepseek_share_decode_step_compiles_for_v5e(
    one_chip, no_compile_cache, monkeypatch
):
    """DeepSeek-V2's served decode step as one chip's share of an expert
    group, at published widths: the dense lead layer and two MoE layers
    holding 20 of the router's 160 experts, 128 slots of 2048 latent
    positions.  The expert kernels compile and read the held stacks in
    place: no value of one layer's ``(20, d, f)`` / ``(20, f, d)`` share."""
    import dataclasses
    import re

    monkeypatch.setattr(moe, "_dual_backend", lambda: "pallas")
    monkeypatch.setattr(attention, "_flash_decode_mode", lambda: "kernel")
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    arch = cut_depth(get_arch("deepseek-v2-236b"), 3)
    arch = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, held=20, expert_exec="dual_path_cost"))
    lm = LM(arch, dtype=BF)
    assert lm.moe_layers_in_place() == 2
    slots, d, f = 128, arch.d_model, arch.moe.d_expert

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = placed(lm.abstract_params())
    cache = placed(jax.eval_shape(lambda: lm.init_cache(slots, MAX_SEQ)))
    batch = placed({
        "tokens": jax.ShapeDtypeStruct((slots, 1), I32),
        "position": jax.ShapeDtypeStruct((slots,), I32),
    })
    text = (
        jax.jit(lm.decode_step, donate_argnums=(2,))
        .lower(params, batch, cache)
        .compile()
        .as_text()
    )
    # the head grouped GEMM and the tail GEMV (MLA decode is plain XLA)
    assert text.count('custom_call_target="tpu_custom_call"') >= 2
    results = {m.group(1) for m in re.finditer(r"= (bf16\[[\d,]+\])\S* [\w\-]+\(", text)}
    assert not {f"bf16[20,{d},{f}]", f"bf16[20,{f},{d}]"} & results
    assert f"bf16[40,{d},{f}]" in results  # the two layers' stacks, viewed whole
