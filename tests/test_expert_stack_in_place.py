"""The dual path's expert kernels read each layer's routed weights from the
layer-stacked arrays in ``prefill`` and ``decode_step``.

The layer loop hands the head and tail kernels ``w_gate`` / ``w_up`` /
``w_down`` whole, viewed as ``(L*E, d, f)`` / ``(L*E, f, d)``, with the
layer's base row ``l*E`` in their scalar-prefetch tables; no layer's
``(E, d, f)`` slice is made.  Held here in interpret mode against the
dense einsum oracle (a wrong base row would run layer 0's experts in
every layer), and on the traced program's shapes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_arch
from repro.models import LM
from repro.models.moe import LOCAL_MESH, MeshInfo

N_LAYERS, B, PROMPT = 3, 6, 6


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_DUAL_BACKEND", "pallas")


def _arch(exec_mode="dual_path", max_head=0):
    """Three MoE layers of 8 experts, top-2.  Six tokens make 12
    assignments, so at most 6 experts take more than one row: a head
    budget of 6 compacts the head (H < E) and drops nothing."""
    arch = get_arch("qwen3-moe-30b-a3b").reduced(n_layers=N_LAYERS)
    return dataclasses.replace(
        arch,
        moe=dataclasses.replace(
            arch.moe, expert_exec=exec_mode, dual_max_head=max_head,
            dual_tail_tokens=1, capacity_factor=8.0, min_capacity=64,
        ),
    )


def _model(arch):
    lm = LM(arch, dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0))


def _inputs(lm, phase):
    key = jax.random.PRNGKey(1)
    if phase == "prefill":
        return ({"tokens": jax.random.randint(key, (1, PROMPT), 1, 255)},)
    batch = {
        "tokens": jax.random.randint(key, (B, 1), 1, 255),
        "position": jnp.arange(B, dtype=jnp.int32) + 3,
    }
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape, a.dtype),
        lm.init_cache(B, 16),
    )
    return batch, cache


def _run(lm, p, phase, inputs):
    fn = lm.prefill if phase == "prefill" else lm.decode_step
    return jax.jit(fn)(p, *inputs)


def _expert_stack_shapes(arch):
    E, d, f = arch.moe.n_experts, arch.d_model, arch.moe.d_expert
    return {(E, d, f), (E, f, d)}


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (scan
    bodies, jitted calls), not looking inside the kernels themselves."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if hasattr(sub, "consts"):  # ClosedJaxpr
                    yield from _eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _kernel_weight_rows(jaxpr, arch):
    """Leading dims of the expert-weight operands of every pallas_call."""
    d, f = arch.d_model, arch.moe.d_expert
    return [
        v.aval.shape[0]
        for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
        for v in eqn.invars
        if v.aval.ndim == 3 and v.aval.shape[1:] in ((d, f), (f, d))
    ]


def _shapes(jaxpr):
    return {
        tuple(v.aval.shape)
        for eqn in _eqns(jaxpr)
        for v in (*eqn.invars, *eqn.outvars)
        if hasattr(v, "aval") and hasattr(v.aval, "shape")
    }


@pytest.mark.parametrize("max_head", [0, 6], ids=["full-head", "head-budget"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_in_place_matches_dense_oracle(phase, max_head):
    arch = _arch(max_head=max_head)
    lm, p = _model(arch)
    assert lm.moe_layers_in_place() == N_LAYERS
    dense, _ = _model(_arch("dense"))
    inputs = _inputs(lm, phase)
    logits, _, aux = _run(lm, p, phase, inputs)
    ref, _, ref_aux = _run(dense, p, phase, inputs)
    assert int(aux.dropped) == int(ref_aux.dropped) == 0
    np.testing.assert_array_equal(np.asarray(aux.counts), np.asarray(ref_aux.counts))
    # the layers route to different experts, so a wrong base row shows
    assert len({tuple(c) for c in np.asarray(aux.counts)}) > 1
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("max_head", [0, 6], ids=["full-head", "head-budget"])
@pytest.mark.parametrize("walk", ["prefill", "decode_step", "forward"])
def test_kernels_take_the_whole_stack(walk, max_head):
    """In ``prefill`` and ``decode_step`` every kernel's weight operand is
    the ``(L*E, ...)`` stack and no value of one layer's stack shape is
    made; ``forward`` (training) keeps the per-layer slice."""
    arch = _arch(max_head=max_head)
    lm, p = _model(arch)
    E = arch.moe.n_experts
    if walk == "forward":
        args = ({"tokens": jnp.ones((1, PROMPT), jnp.int32)},)
    else:
        args = _inputs(lm, "prefill" if walk == "prefill" else "decode")
    jaxpr = jax.make_jaxpr(getattr(lm, walk))(p, *args).jaxpr
    rows = _kernel_weight_rows(jaxpr, arch)
    assert len(rows) == 6  # w_gate, w_up, w_down of the head and the tail
    stack_shapes = _expert_stack_shapes(arch)
    if walk == "forward":
        assert set(rows) == {E}
        assert stack_shapes <= _shapes(jaxpr)
    else:
        assert set(rows) == {N_LAYERS * E}
        assert not stack_shapes & _shapes(jaxpr)


@pytest.mark.parametrize(
    "case", ["pallas", "xla", "dense", "ep-mesh"],
)
def test_engages_only_on_the_local_pallas_path(case, monkeypatch):
    mi = LOCAL_MESH
    exec_mode = "dual_path_cost"
    if case == "xla":
        monkeypatch.setenv("REPRO_DUAL_BACKEND", "xla")
    elif case == "dense":
        exec_mode = "dense"
    elif case == "ep-mesh":
        mi = MeshInfo(AbstractMesh((2,), ("model",)), (), "model")
    arch = _arch(exec_mode)
    lm = LM(arch, dtype=jnp.float32, mesh_info=mi)
    expected = N_LAYERS if case == "pallas" else 0
    assert lm.moe_layers_in_place() == expected
