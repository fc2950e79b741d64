"""The reduction from a profiler trace to device numbers: exact on a
synthetic case, and sound on a short trace of the qwen3 decode cell
recorded on one TPU v5e and kept beside this file."""

import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from benchmark import readers, trace as tr

SAMPLE = Path(__file__).parent / "data" / "qwen3_decode.xplane.pb.gz"


def ev(*items):
    return tr.Events.of([(n, s, d) for n, s, d in items])


def test_op_names():
    hlo = "%swiglu_gemv.7 = bf16[128,1,2048]{2,1,0} custom-call(s32[128] %swiglu_gmm_capacity.3)"
    assert tr.op_name(hlo) == "swiglu_gemv.7"
    assert tr.op_kind(hlo) == "swiglu_gemv"
    assert ev((hlo, 0, 1)).of_kind(["swiglu_gmm_capacity"]).names == []


def test_op_shapes():
    assert tr.op_shape("%f.3 = bf16[128,2048,768]{2,1,0:T(8,128)(2,1)} fusion(s32[] %p)") \
        == (128, 2048, 768)
    assert tr.op_shape("%t = (bf16[4]{0}, s32[]) tuple(...)") == (4,)
    assert tr.op_shape("%c = f32[] constant(0)") == ()
    assert tr.op_shape("no result") is None
    ops = ev(("%a.1 = bf16[8,4]{1,0} fusion()", 0, 5), ("%b.1 = bf16[4,8]{1,0} copy()", 5, 5))
    assert ops.of_shape([(4, 8)]).names == ["%b.1 = bf16[4,8]{1,0} copy()"]


def test_union_busy_inside_and_gaps_exact():
    ops = ev(("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("a", 50, 10))
    assert tr.union(ops) == [(0, 15), (30, 35), (50, 60)]
    assert tr.busy_ns(ops) == 30
    assert tr.by_name(ops) == {"a": 20, "b": 10, "c": 5}
    progs = ev(("decode", 0, 20), ("prefill", 45, 20))
    assert tr.inside(ops, progs.matching(["decode"])).names == ["a", "b"]
    host = ev(("bench/engine_step", 14, 20), ("bench/generate", 36, 20))
    gaps = tr.idle_gaps(ops, host)
    assert [n for n, _ in gaps] == ["bench/engine_step", "bench/generate"]
    assert np.allclose([g for _, g in gaps], [15e-9, 15e-9])


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    if not SAMPLE.exists():
        pytest.skip("no recorded trace")
    path = tmp_path_factory.mktemp("trace") / "sample.xplane.pb"
    with gzip.open(SAMPLE) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return tr.load(str(path))


def test_recorded_trace_reduces_soundly(sample):
    dev = tr.first_device(sample)
    ops, mods = sample.ops[dev], sample.modules[dev]
    assert len(ops) > 100 and len(mods) > 0
    iv = tr.union(ops)
    assert all(a <= b for a, b in iv)
    assert all(iv[k][1] < iv[k + 1][0] for k in range(len(iv) - 1))
    span = ops.end.max() - ops.start.min()
    assert 0 < tr.busy_ns(ops) <= min(span, ops.total())
    decode = mods.matching([readers.DECODE_PROGRAM])
    assert len(decode) > 0
    experts = tr.inside(ops.of_kind(readers.EXPERT_KERNELS), decode)
    attn = tr.inside(ops.of_kind(readers.ATTENTION_KERNELS), decode)
    assert len(experts) > 0 and len(attn) > 0
    assert experts.total() < decode.total()
    gaps = tr.idle_gaps(ops, sample.host)
    assert gaps and all(g > 0 for _, g in gaps)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert {n for n, _ in gaps} <= set(sample.host.names) | {"none"}


def test_expert_path_time_holds_the_expert_stack_copies(sample):
    """The expert path's device time in the recorded qwen3 decode steps is
    the two expert kernels plus the three copies of each layer's expert
    stacks (w_gate, w_up: 128x2048x768; w_down: 128x768x2048), one of each
    per layer and step, and no KV-cache copy."""
    from types import SimpleNamespace

    dm = SimpleNamespace(n_experts=128, d=2048, d_expert=768)
    dev = tr.first_device(sample)
    ctx = SimpleNamespace(trace=sample, dev=dev, dm=dm)
    ops, mods = sample.ops[dev], sample.modules[dev]
    decode = mods.matching([readers.DECODE_PROGRAM])
    kernels = tr.inside(ops.of_kind(readers.EXPERT_KERNELS), decode)
    stacks = tr.inside(ops.of_shape(readers.expert_stack_shapes(dm)), decode)
    stacks = stacks.select([tr.op_kind(n) not in readers.EXPERT_KERNELS for n in stacks.names])
    n_layer_steps = 8 * len(decode)
    assert len(stacks) == 3 * n_layer_steps
    assert {tr.op_kind(n) for n in stacks.names} == {"dynamic-slice_bitcast_fusion"}
    assert all(tr.op_shape(n)[0] == 128 for n in stacks.names)
    total = readers.expert_path_time(ctx)
    assert total == pytest.approx((kernels.total() + stacks.total()) * 1e-9)
    # the copies cost more than either kernel (PERF.md, where the time goes)
    assert stacks.total() > kernels.total() / 2
    assert total < decode.total() * 1e-9
