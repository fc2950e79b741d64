"""Tests for the §Perf optimization paths: jit scheduler, sequence-parallel
decode attention, int8 KV cache."""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import CostModel, MoELayerSpec, b200_pim_system
from repro.core.scheduler import sieve_schedule
from repro.core.scheduler_jax import SieveParams, export_cost_table, sieve_partition_jax

LAYER = MoELayerSpec(d_model=2048, d_ff=768, n_experts=32, top_k=8)


class TestJitScheduler:
    @given(
        counts=st.lists(st.integers(0, 40), min_size=4, max_size=32).map(
            lambda x: np.asarray(x, np.int32)
        )
    )
    @settings(max_examples=25, deadline=None)
    def test_matches_python_argmin(self, counts):
        """The vectorized in-graph scheduler == the python prefix-argmin."""
        cm = CostModel(system=b200_pim_system(), layer=LAYER, pim_attn_time=2e-6)
        table = export_cost_table(None, cm, max_count=64)
        params = SieveParams.from_cost_model(cm, int(counts.sum()))
        out = sieve_partition_jax(jnp.asarray(counts), jnp.asarray(table), params)
        ref = sieve_schedule(counts, cm, mode="argmin")
        # same split size and same GPU set
        assert int(out["split"]) == len(ref.gpu_experts)
        got_gpu = set(np.nonzero(np.asarray(out["gpu_mask"]))[0].tolist())
        assert got_gpu == set(ref.gpu_experts.tolist())
        assert float(out["t_total"]) == pytest.approx(ref.t_total, rel=1e-4)

    def test_jit_compiles_once(self):
        cm = CostModel(system=b200_pim_system(), layer=LAYER)
        table = jnp.asarray(export_cost_table(None, cm, 64))
        params = SieveParams.from_cost_model(cm, 64)
        f = lambda c: sieve_partition_jax(c, table, params)
        a = f(jnp.arange(32, dtype=jnp.int32))
        b = f(jnp.arange(32, dtype=jnp.int32)[::-1])
        assert a["gpu_mask"].shape == b["gpu_mask"].shape


def _run_subprocess(script: str, marker: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert marker in r.stdout, r.stderr[-2000:]


def test_seqpar_decode_matches_reference():
    """Sequence-parallel decode attention (§Perf A1) is numerically exact."""
    _run_subprocess(
        r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs.base import AttnConfig
from repro.models.attention import gqa_decode, gqa_decode_seqpar, init_gqa
from repro.models.moe import MeshInfo

cfg = AttnConfig(kind="gqa", n_heads=8, n_kv_heads=2, d_head=16, rope_theta=1e4)
p = init_gqa(jax.random.PRNGKey(0), cfg, 64, jnp.float32)
B, T = 4, 32
ks = jax.random.split(jax.random.PRNGKey(1), 3)
x = jax.random.normal(ks[0], (B, 1, 64))
ck = jax.random.normal(ks[1], (B, 2, T, 16))  # head-major
cv = jax.random.normal(ks[2], (B, 2, T, 16))
pos = jnp.array([5, 0, 31, 17], jnp.int32)
y_ref, ck_ref, cv_ref = gqa_decode(p, x, pos, ck, cv, cfg)
from repro.launch.mesh import make_mesh, use_mesh
mesh = make_mesh((2, 4), ("data", "model"))
mi = MeshInfo(mesh=mesh, data_axes=("data",), model_axis="model")
with use_mesh(mesh):
    y_sp, (ck_sp, cv_sp) = jax.jit(
        lambda *a: gqa_decode_seqpar(p, a[0], a[1], a[2], a[3], cfg, mi)
    )(x, pos, ck, cv)
assert float(jnp.max(jnp.abs(y_ref - y_sp))) < 1e-4
assert float(jnp.max(jnp.abs(ck_ref - ck_sp))) < 1e-5
print("SEQPAR-OK")
""",
        "SEQPAR-OK",
    )


def test_int8_kv_bounded_error():
    """int8 KV (§Perf A2) stays within 3% of the fp path over multiple steps."""
    _run_subprocess(
        r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs.base import AttnConfig
from repro.models.attention import gqa_decode_seqpar, init_gqa
from repro.models.moe import MeshInfo

cfg = AttnConfig(kind="gqa", n_heads=8, n_kv_heads=2, d_head=16, rope_theta=1e4)
p = init_gqa(jax.random.PRNGKey(0), cfg, 64, jnp.float32)
B, T = 4, 32
x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, 64))
from repro.launch.mesh import make_mesh, use_mesh
mesh = make_mesh((2, 4), ("data", "model"))
mi = MeshInfo(mesh=mesh, data_axes=("data",), model_axis="model")
ck = jnp.zeros((B, 2, T, 16)); cv = jnp.zeros((B, 2, T, 16))  # head-major
ck8 = jnp.zeros((B, 2, T, 16), jnp.int8); cv8 = jnp.zeros((B, 2, T, 16), jnp.int8)
ks8 = jnp.zeros((B, 2, T)); vs8 = jnp.zeros((B, 2, T))
with use_mesh(mesh):
    f_ref = jax.jit(lambda *a: gqa_decode_seqpar(p, a[0], a[1], a[2], a[3], cfg, mi))
    f_q = jax.jit(lambda *a: gqa_decode_seqpar(p, a[0], a[1], a[2], a[3], cfg, mi, kv_scales=(a[4], a[5])))
    for t in range(6):
        xt = jax.random.normal(jax.random.PRNGKey(10 + t), (B, 1, 64))
        post = jnp.full((B,), t, jnp.int32)
        y_ref, (ck, cv) = f_ref(xt, post, ck, cv)
        y_q, (ck8, cv8, ks8, vs8) = f_q(xt, post, ck8, cv8, ks8, vs8)
rel = float(jnp.max(jnp.abs(y_ref - y_q)) / (jnp.max(jnp.abs(y_ref)) + 1e-9))
assert rel < 0.03, rel
print("INT8-OK")
""",
        "INT8-OK",
    )
