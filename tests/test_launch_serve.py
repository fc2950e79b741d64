"""Serving launcher: depth cut, jitted init, compile-cache placement."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.launch import serve
from repro.serving import BatchingConfig


def test_cut_depth_keeps_every_width():
    arch = get_arch("qwen3-moe-30b-a3b")
    cut = serve.cut_depth(arch, 8)
    assert cut.n_layers == 8
    assert (cut.d_model, cut.attn, cut.moe, cut.vocab_size) == (
        arch.d_model, arch.attn, arch.moe, arch.vocab_size
    )


@pytest.mark.parametrize("n", [0, 49])
def test_cut_depth_rejects_out_of_range(n):
    with pytest.raises(ValueError, match="--layers"):
        serve.cut_depth(get_arch("qwen3-moe-30b-a3b"), n)


def test_cut_depth_keeps_dense_prefix_layers():
    arch = get_arch("deepseek-v2-236b")  # first_k_dense=1
    with pytest.raises(ValueError):
        serve.cut_depth(arch, 1)
    assert serve.cut_depth(arch, 2).n_layers == 2


def test_build_engine_jitted_init_matches_eager():
    arch = serve.build_arch("qwen3-moe-30b-a3b", layers=1)
    eng = serve.build_engine(
        arch, BatchingConfig(n_slots=2, max_seq=32), dtype=jnp.float32, seed=3
    )
    eager = eng.lm.init(jax.random.PRNGKey(3))
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(eager)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert jnp.allclose(a, b, rtol=1e-6, atol=1e-6)


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = serve.use_compile_cache()
        assert path == str(serve.REPO_CACHE_DIR)
        assert serve.REPO_CACHE_DIR.name == ".jax_cache"
        assert (serve.REPO_CACHE_DIR.parent / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize(
    "mesh_shape,paged,cache_spec",
    [
        ((1, 2), False, 'P(None, ("data",), "model", None, None)'),
        # the paged pool is shared by every slot: its odd block count stays
        # whole on each data shard and only kv heads split
        ((2, 2), True, 'P(None, None, "model", None, None)'),
    ],
    ids=["dense-1x2", "paged-2x2"],
)
def test_mesh_engine_matches_local_engine(mesh_shape, paged, cache_spec):
    """Params and cache are created sharded over a (data, model) mesh
    (experts and kv heads split), the decode-attention kernel runs per kv-head
    shard, and the served tokens equal the single-device engine's (fake
    devices, in a subprocess so this process keeps one)."""
    import os
    import subprocess
    import sys

    n_dev = mesh_shape[0] * mesh_shape[1]
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.models import attention, moe
attention._flash_decode_mode = lambda: "kernel"
moe._dual_backend = lambda: "pallas"
from repro.launch.mesh import make_mesh, mesh_info_for
from repro.launch.serve import build_arch, build_engine
from repro.serving import BatchingConfig, Request

arch = build_arch("qwen3-moe-30b-a3b", layers=2)
assert arch.attn.n_kv_heads == 2
batching = BatchingConfig(n_slots=2, max_seq=32, paged={paged}, page_size=8)
mesh = make_mesh({mesh_shape}, ("data", "model"))
outs = []
for mi in (mesh_info_for(mesh, 2), None):
    kw = {{"mesh_info": mi}} if mi is not None else {{}}
    eng = build_engine(arch, batching, dtype=jnp.float32, **kw)
    if mi is not None:
        assert eng.cache["blocks"][0].sharding.spec == {cache_spec}, eng.cache["blocks"][0].sharding
        assert eng.params["blocks"]["moe"]["w_up"].sharding.spec == P(None, "model", None, None)
    for i in range(2):
        eng.submit(Request(prompt=[1 + i, 7, 3, 9, 5], max_new_tokens=4))
    outs.append(sorted(tuple(r.generated) for r in eng.run_until_done()))
assert outs[0] == outs[1], outs
print("MESH-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=300,
    )
    assert "MESH-OK" in r.stdout, r.stderr[-2000:]
