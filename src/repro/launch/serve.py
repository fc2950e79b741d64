"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the Sieve serving engine (continuous batching + scheduler-in-loop)
on the requested arch and runs a synthetic request workload, reporting
throughput/interactivity and the Sieve partition trail.

``--full`` serves the published widths in bf16; ``--layers N`` cuts the
depth to the first N layers and keeps every width.  Parameters are built
by one jitted init, so no float32 copy of a weight stack is ever held on
the device, and on a mesh they are created already sharded.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.models import LM
from repro.models.moe import LOCAL_MESH, MeshInfo
from repro.models.sharding import param_pspecs, to_shardings
from repro.serving import BatchingConfig, Request, ServingEngine

# fixed, gitignored persistent-compile-cache location: the path is part of
# the cache key, so it never comes from a temp name, a pid or the clock
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is overridden; otherwise the cache is the repo's
    ``.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def cut_depth(arch: ArchConfig, n_layers: int) -> ArchConfig:
    """The first ``n_layers`` layers of ``arch`` at unchanged widths."""
    n_prefix = arch.moe.first_k_dense if arch.moe is not None else 0
    if not n_prefix < n_layers <= arch.n_layers:
        raise ValueError(
            f"--layers {n_layers} outside ({n_prefix}, {arch.n_layers}] "
            f"for {arch.name}"
        )
    return dataclasses.replace(arch, n_layers=n_layers)


def build_arch(
    name: str, *, full: bool = False, layers: Optional[int] = None
) -> ArchConfig:
    arch = get_arch(name)
    if not full:
        arch = arch.reduced()
    if layers is not None:
        arch = cut_depth(arch, layers)
    return arch


def init_params(lm: LM, seed: int) -> Any:
    """Params from one jitted init (sharded by ``param_pspecs`` on a mesh)."""
    mi = lm.mi
    out_shardings = None
    if mi.mesh is not None:
        specs = param_pspecs(
            lm.abstract_params(), lm.arch,
            model_axis=mi.model_axis, model_size=mi.ep_size,
        )
        out_shardings = to_shardings(mi.mesh, specs)
    return jax.jit(lm.init, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed)
    )


def build_engine(
    arch: ArchConfig,
    batching: BatchingConfig,
    *,
    dtype=jnp.bfloat16,
    seed: int = 0,
    mesh_info: MeshInfo = LOCAL_MESH,
    params: Any = None,
    **engine_kw,
) -> ServingEngine:
    """LM + params + :class:`ServingEngine` for ``arch``.  Pass ``params``
    to serve existing weights (same tree) under another arch variant."""
    lm = LM(arch, dtype=dtype, mesh_info=mesh_info)
    if params is None:
        params = init_params(lm, seed)
    return ServingEngine(lm, params, batching, seed=seed, **engine_kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b")
    ap.add_argument("--policy", default="sieve",
                    choices=["sieve", "sieve_argmin", "pimoe", "noexp", "allexp"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--colocated-pd", action="store_true")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve only the first N layers (widths unchanged)")
    args = ap.parse_args(argv)

    use_compile_cache()
    arch = build_arch(args.arch, full=not args.reduced, layers=args.layers)
    engine = build_engine(
        arch,
        BatchingConfig(n_slots=args.slots, max_seq=args.max_seq,
                       colocated_pd=args.colocated_pd),
        dtype=jnp.float32 if args.reduced else jnp.bfloat16,
        policy=args.policy,
    )
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        engine.submit(Request(
            prompt=list(rng.integers(0, arch.vocab_size - 1, args.prompt_len)),
            max_new_tokens=args.max_new, arrival_time=time.perf_counter(),
        ))
    done = engine.run_until_done()
    dt = time.time() - t0

    total_new = sum(len(r.generated) for r in done)
    ttfts = [r.first_token_time - r.arrival_time for r in done
             if r.first_token_time]
    print(f"arch={arch.name} layers={arch.n_layers} policy={args.policy} "
          f"device={jax.devices()[0].device_kind}")
    print(f"served {len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s, compile included)")
    if ttfts:
        print(f"TTFT p50={np.median(ttfts)*1e3:.1f}ms p max={max(ttfts)*1e3:.1f}ms")
    if engine.is_moe and engine.stats.partitions:
        parts = engine.stats.partitions
        gpu_frac = np.mean([p["n_gpu"] / max(p["n_gpu"] + p["n_pim"], 1)
                            for p in parts])
        print(f"sieve: {len(parts)} layer-partitions, "
              f"mean GPU-expert fraction={gpu_frac:.2f}, "
              f"cost-table coverage={engine.cost_table.coverage} token-counts")


if __name__ == "__main__":
    main()
