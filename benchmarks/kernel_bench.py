"""Kernel + model-step wall-time microbenchmarks (XLA:CPU).

Wall times here are CPU numbers (the container has no TPU); they validate
that the jit'd paths run and give the derived MXU-padding-waste metric that
motivates the Sieve dual path.  TPU projections live in §Roofline.

Runs standalone with a CLI (``--quick`` is the CI perf-smoke mode: kernel
rows only, fewer iters, JSON artifact to ``benchmarks/out``) or through
``benchmarks.run`` alongside the paper figures.

``--check`` gates the paged-decode padding win: the pool-major XLA twin at
mixed sequence lengths must beat ``decode_attention_ref`` padded to
max_seq by the committed floor (and stay within 2x of the baseline ratio
in ``benchmarks/BENCH_kernel.json``; regenerate with
``--quick --update-baseline`` after an intentional change).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.kernels import ops, ref
from repro.models import LM
from repro.models import attention as attn_lib

try:
    from .common import Rows, add_trace_arg, time_fn, trace_session
except ImportError:  # invoked as a script: python benchmarks/kernel_bench.py
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import Rows, add_trace_arg, time_fn, trace_session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "benchmarks", "BENCH_kernel.json")

# paged-decode gate: the pool-major XLA twin at mixed sequence lengths
# must beat the dense reference padded to max_seq by at least this much
# (compute/traffic ∝ allocated pool blocks, not B×max_seq) — the
# serving-level padding win the paged KV cache exists for
GATE_MIN_PAGED_TWIN_SPEEDUP = 1.5


def kernels() -> Rows:
    rows = Rows()
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)

    # grouped GEMM: capacity layout, 25% fill (the bimodal regime)
    E, C, K, N = 16, 64, 256, 256
    buf = jax.random.normal(ks[0], (E, C, K), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, K, N), jnp.float32)
    sizes = jnp.asarray(np.random.default_rng(0).integers(0, C // 4, size=E), jnp.int32)
    out = ops.gmm_capacity(buf, rhs, sizes, bm=8, bk=128, bn=128, interpret=True)
    out.block_until_ready()
    us = time_fn(
        lambda: ops.gmm_capacity(buf, rhs, sizes, bm=8, bk=128, bn=128,
                                 interpret=True).block_until_ready(),
        warmup=1, iters=3,
    )
    fill = float(sizes.sum()) / (E * C)
    rows.add("kernel/gmm_capacity_interp", us, f"fill={fill:.2f};mxu_skip={1-fill:.2f}")

    # reference einsum path (what the runtime uses on CPU)
    def einsum_path():
        jnp.einsum("ecd,edf->ecf", buf, rhs).block_until_ready()

    rows.add("kernel/gmm_dense_einsum", time_fn(einsum_path, iters=5),
             "padding_flops_fraction=%.2f" % (1 - fill))

    # expert gemv
    S = 32
    toks = jax.random.normal(ks[2], (S, K), jnp.float32)
    eids = jnp.asarray(np.random.default_rng(1).integers(0, E, size=S), jnp.int32)
    us = time_fn(
        lambda: ops.expert_gemv(toks, rhs, eids, None, bk=128, bn=128,
                                interpret=True).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/expert_gemv_interp", us, f"S={S}")

    # decode attention
    B, H, Kv, dh, T = 8, 16, 4, 64, 1024
    q = jax.random.normal(ks[3], (B, H, dh), jnp.float32)
    ck = jax.random.normal(ks[4], (B, Kv, T, dh), jnp.float32)
    cv = jax.random.normal(ks[5], (B, Kv, T, dh), jnp.float32)
    lens = jnp.full((B,), T, jnp.int32)
    us = time_fn(
        lambda: ops.decode_attention(q, ck, cv, lens, bt=256,
                                     interpret=True).block_until_ready(),
        warmup=1, iters=3,
    )
    kv_bytes = 2 * B * T * Kv * dh * 4
    rows.add("kernel/decode_attention_interp", us, f"kv_bytes={kv_bytes}")
    us_ref = time_fn(
        lambda: ref.decode_attention_ref(q, ck, cv, lens).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/decode_attention_ref", us_ref, "")

    # flash decode at ragged (mixed) lengths: T=1024 with bt=256 means the
    # short rows skip dead tiles entirely — plus the T % bt != 0 tail path
    mixed = np.array([64, 128, 256, 384, 512, 640, 896, 1024])
    lens_mixed = jnp.asarray(mixed, jnp.int32)
    us_ragged = time_fn(
        lambda: ops.decode_attention(q, ck, cv, lens_mixed, bt=256,
                                     interpret=True).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/flash_decode_ragged_interp", us_ragged,
             f"mean_len={mixed.mean():.0f};ratio_vs_ref={us_ragged / us_ref:.2f}")
    us_split = time_fn(
        lambda: ops.decode_attention(q, ck, cv, lens_mixed, bt=256,
                                     n_splits=4,
                                     interpret=True).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/flash_decode_split4_interp", us_split,
             f"ratio_vs_ref={us_split / us_ref:.2f}")
    return rows


def paged_decode() -> Rows:
    """Paged (block-table) decode attention: the Pallas kernel in interpret
    mode and its pool-major XLA twin (the CPU serving path), each against
    ``decode_attention_ref`` padded to max_seq.  The twin's speedup is the
    padding win — compute ∝ allocated blocks, not B×max_seq — and is the
    gated number (``--check``)."""
    rows = Rows()
    B, H, Kv, dh, T, page = 8, 16, 4, 64, 1024, 64
    mixed = np.array([64, 128, 256, 384, 512, 640, 896, 1024])
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    ck = jax.random.normal(ks[1], (B, Kv, T, dh), jnp.float32)
    cv = jax.random.normal(ks[2], (B, Kv, T, dh), jnp.float32)
    lens = jnp.asarray(mixed, jnp.int32)

    # pack the dense cache into a block pool sized to the allocated blocks
    nb = T // page
    n_pool = int((-(-mixed // page)).sum()) + 1  # +1 trash block
    tab = np.zeros((B, nb), np.int32)
    owner = np.full((n_pool,), -1, np.int32)
    bpos = np.zeros((n_pool,), np.int32)
    pool_k = np.zeros((n_pool, Kv, page, dh), np.float32)
    pool_v = np.zeros_like(pool_k)
    ck_np, cv_np = np.asarray(ck), np.asarray(cv)
    nxt = 1
    for b in range(B):
        for j in range(-(-int(mixed[b]) // page)):
            tab[b, j] = nxt
            owner[nxt], bpos[nxt] = b, j
            pool_k[nxt] = ck_np[b, :, j * page:(j + 1) * page]
            pool_v[nxt] = cv_np[b, :, j * page:(j + 1) * page]
            nxt += 1
    pk, pv = jnp.asarray(pool_k), jnp.asarray(pool_v)
    tab_j = jnp.asarray(tab)
    owner_j, bpos_j = jnp.asarray(owner), jnp.asarray(bpos)
    pool_frac = (n_pool - 1) / (B * nb)

    us_ref = time_fn(
        lambda: ref.decode_attention_ref(q, ck, cv, lens).block_until_ready(),
        warmup=1, iters=5,
    )
    rows.add("kernel/paged_ref_padded", us_ref,
             f"kv_tokens={B * T};pool_tokens={(n_pool - 1) * page}")
    us_paged = time_fn(
        lambda: ops.decode_attention_paged(
            q, pk, pv, tab_j, lens, interpret=True
        ).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/paged_decode_interp", us_paged,
             f"page={page};ratio_vs_ref={us_paged / us_ref:.2f}")

    twin = jax.jit(attn_lib.paged_decode_attention_xla)
    q4 = q[:, None]
    us_twin = time_fn(
        lambda: twin(q4, pk, pv, owner_j, bpos_j, lens).block_until_ready(),
        warmup=1, iters=5,
    )
    rows.add(
        "kernel/paged_decode_xla_twin", us_twin,
        f"pool_frac={pool_frac:.2f};twin_speedup={us_ref / us_twin:.2f}",
    )
    return rows


def fused_swiglu() -> Rows:
    """Fused single-pass SwiGLU kernels vs the three-call formulations
    (interpret mode, compacted hot-expert head slab + streaming tail)."""
    rows = Rows()
    ks = jax.random.split(jax.random.PRNGKey(2), 5)

    # grouped head path: H hot experts with near-full capacity slabs
    H, C, K, F = 8, 64, 128, 128
    slab = jax.random.normal(ks[0], (H, C, K), jnp.float32)
    wg = jax.random.normal(ks[1], (H, K, F), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (H, K, F), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (H, F, K), jnp.float32) * 0.1
    sizes = jnp.asarray(
        np.random.default_rng(0).integers(C // 2, C + 1, size=H), jnp.int32
    )
    us_fused = time_fn(
        lambda: ops.swiglu_gmm_capacity(
            slab, wg, wu, wd, sizes, bm=16, interpret=True
        ).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/swiglu_fused_interp", us_fused, f"H={H};C={C}")

    def three_call():
        gate = ops.gmm_capacity(slab, wg, sizes, bm=16, interpret=True)
        up = ops.gmm_capacity(slab, wu, sizes, bm=16, interpret=True)
        h = jax.nn.silu(gate) * up
        ops.gmm_capacity(h, wd, sizes, bm=16, interpret=True).block_until_ready()

    us_three = time_fn(three_call, warmup=1, iters=3)
    rows.add(
        "kernel/swiglu_threecall_interp", us_three,
        f"fused_speedup={us_three / us_fused:.2f}",
    )

    # streaming tail: one fused pass vs three expert_gemv streams
    S = 16
    toks = jax.random.normal(ks[4], (S, K), jnp.float32)
    eids = jnp.asarray(np.random.default_rng(1).integers(0, H, size=S), jnp.int32)
    us_gemv_fused = time_fn(
        lambda: ops.swiglu_gemv(
            toks, wg, wu, wd, eids, None, bk=128, bf=128, interpret=True
        ).block_until_ready(),
        warmup=1, iters=3,
    )
    rows.add("kernel/swiglu_gemv_fused_interp", us_gemv_fused, f"S={S}")

    def three_gemv():
        gate = ops.expert_gemv(toks, wg, eids, None, bk=128, bn=128, interpret=True)
        up = ops.expert_gemv(toks, wu, eids, None, bk=128, bn=128, interpret=True)
        h = jax.nn.silu(gate) * up
        ops.expert_gemv(h, wd, eids, None, bk=128, bn=128, interpret=True).block_until_ready()

    us_gemv_three = time_fn(three_gemv, warmup=1, iters=3)
    rows.add(
        "kernel/swiglu_gemv_threecall_interp", us_gemv_three,
        f"fused_speedup={us_gemv_three / us_gemv_fused:.2f}",
    )
    return rows


def model_steps() -> Rows:
    """Reduced-arch step wall times (train + decode) on CPU."""
    rows = Rows()
    for name in ("qwen3-moe-30b-a3b", "granite-3-2b", "rwkv6-7b"):
        arch = get_arch(name).reduced()
        lm = LM(arch, dtype=jnp.float32)
        p = lm.init(jax.random.PRNGKey(0))
        B, S = 2, 32
        t = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, arch.vocab_size)
        loss = jax.jit(lambda p, b: lm.loss(p, b)[0])
        batch = {"tokens": t, "labels": t}
        loss(p, batch).block_until_ready()
        rows.add(f"model/{name}/loss_fwd", time_fn(
            lambda: loss(p, batch).block_until_ready(), warmup=1, iters=5),
            f"tokens={B*S}")
        cache = lm.init_cache(B, S)
        db = {"tokens": t[:, :1], "position": jnp.zeros((B,), jnp.int32)}
        step = jax.jit(lm.decode_step)
        step(p, db, cache)[0].block_until_ready()
        rows.add(f"model/{name}/decode_step", time_fn(
            lambda: step(p, db, cache)[0].block_until_ready(), warmup=1, iters=5),
            "")
    return rows


ALL = [kernels, paged_decode, fused_swiglu, model_steps]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--quick", action="store_true",
        help="CI perf-smoke mode: kernel rows only (skips model steps)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="exit nonzero if the paged XLA twin's mixed-length speedup "
        f"over the padded reference falls below "
        f"{GATE_MIN_PAGED_TWIN_SPEEDUP}x or regresses >2x vs the baseline",
    )
    ap.add_argument(
        "--update-baseline", action="store_true",
        help=f"also write results to {BASELINE_PATH}",
    )
    ap.add_argument(
        "--out", default=os.path.join("benchmarks", "out", "kernel_bench.json")
    )
    add_trace_arg(ap)
    args = ap.parse_args(argv)

    fns = [kernels, paged_decode, fused_swiglu] if args.quick else list(ALL)
    print("name,us_per_call,derived")
    records = []
    with trace_session(args.trace_out, "kernel_bench") as tel:
        for fn in fns:
            with tel.span(f"bench/{fn.__name__}"):
                rows = fn()
            rows.emit()
            records.extend(rows.to_records())
    by_name = {r["name"]: r for r in records}
    report = {"quick": args.quick, "rows": records}
    ref_row = by_name.get("kernel/paged_ref_padded")
    twin_row = by_name.get("kernel/paged_decode_xla_twin")
    if ref_row and twin_row:
        report["paged_twin_speedup"] = round(
            ref_row["us_per_call"] / twin_row["us_per_call"], 3
        )

    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}", file=sys.stderr)
    if args.update_baseline:
        with open(BASELINE_PATH, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {BASELINE_PATH}", file=sys.stderr)

    if args.check:
        failures = []
        got = report.get("paged_twin_speedup")
        if got is None:
            failures.append("paged decode rows missing from this run")
        elif got < GATE_MIN_PAGED_TWIN_SPEEDUP:
            failures.append(
                f"paged XLA twin speedup {got:.2f}x < "
                f"{GATE_MIN_PAGED_TWIN_SPEEDUP}x floor over the padded "
                "reference at mixed lengths"
            )
        if got is not None and os.path.exists(BASELINE_PATH):
            with open(BASELINE_PATH) as f:
                base = json.load(f)
            want = base.get("paged_twin_speedup")
            # in-run ratio, machine-independent (cf. moe_bench gates)
            if want and got < want / 2.0:
                failures.append(
                    f"paged XLA twin speedup {got:.2f}x < baseline "
                    f"{want:.2f}x / 2"
                )
        elif got is not None:
            print("no committed baseline; floor check only", file=sys.stderr)
        if failures:
            print(
                "PERF REGRESSION:\n  " + "\n  ".join(failures),
                file=sys.stderr,
            )
            sys.exit(1)
        print("perf check OK", file=sys.stderr)
    return report


if __name__ == "__main__":
    main()
