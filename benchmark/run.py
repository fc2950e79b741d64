"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration (``benchmark/configs/<name>.json``) and a traffic mix
(``benchmark/traffic/<name>.json``); each per-layer metric is read by
``benchmark/metrics/<name>.py``; the configuration names its plain
reference, ``benchmark/reference/<name>.py``.  Later cells, mixes,
metrics and references are new files; nothing here names one.

One process owns the chip for the whole run:

1. build the program's arch from the configuration file;
2. make the weights on the device from the seed and shape the routers for
   the mix's expert popularity;
3. build the program's ``ServingEngine`` on them and warm every shape the
   mix uses (one prefill per prompt bucket, the decode step);
4. pre-roll, then measure for ``--seconds`` (``--trace 1`` records a
   profiler trace of the window and the program's telemetry spans);
5. read the peak device memory, free the engine, and compare a sample of
   the window's finished requests with the float32 reference.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
P95 = 95
TRACE_SECONDS = 10.0  # a --trace 1 run traces the window's first seconds


def gap_stats(gaps) -> Dict:
    """Widest, mean and 99th-percentile gap, and the share of served tokens
    that are not the reference's best."""
    g = np.asarray(gaps, np.float64)
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)), "disagree": float((g > 0).mean())}


# ---------------------------------------------------------------------------
# Finding cells, configurations, mixes and metrics by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config: Dict
    mix: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]
    limits: Dict


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, name: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "benchmark" / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "benchmark" / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, cfg, mix, int(w["chips"]), e2e, layer, limits)


_MODULES: Dict[str, object] = {}


def load_module(path: Path, prefix: str):
    """A module of the benchmark found by its file name (once per process,
    so its compiled functions are kept from run to run)."""
    key = str(path.resolve())
    if key not in _MODULES:
        if not path.is_file():
            raise SystemExit(f"no such file: {path}")
        name = f"{prefix}_{len(_MODULES)}_{path.stem.replace('.', '_').replace('-', '_')}"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod  # dataclasses look their module up there
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def metric_reader(root: Path, name: str):
    return load_module(root / "benchmark" / "metrics" / f"{name}.py", "benchmark_metric").read


def reference_module(root: Path, cfg: Dict):
    """The configuration's plain reference, ``benchmark/reference/<name>.py``."""
    return load_module(root / "benchmark" / "reference" / f"{cfg['reference']}.py",
                       "benchmark_reference")


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def use_chip(chips: int, require_tpu: bool):
    """The devices of the run; exits non-zero (no result) without a TPU
    or with fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    print(f"platform {plat}, device_kind {devs[0].device_kind}, devices {len(devs)}",
          file=sys.stderr)
    if require_tpu and plat != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {plat!r}; this benchmark never "
                         "falls back to another backend")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program, however small."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_entry(kind: str) -> Dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# End-to-end arithmetic
# ---------------------------------------------------------------------------


def pct(xs, q) -> Optional[float]:
    """Percentile with linear interpolation (numpy's default), as
    repro.cluster.metrics.percentiles computes it; inf counts as a miss."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, float), q))


def end_to_end(served, t0: float, t1: float) -> Dict:
    window = t1 - t0
    n_tokens, tpots = 0, []
    for s in served:
        tt = np.asarray(s.token_times)
        inw = tt[(tt >= t0) & (tt <= t1)]
        n_tokens += len(inw)
        # per request, the mean gap over its in-window tokens, where they
        # span a quarter second or more of host clock
        if len(inw) >= 2 and inw[-1] - inw[0] >= 0.25:
            tpots.append((inw[-1] - inw[0]) / (len(inw) - 1))
    return {
        "output_tokens_per_s": n_tokens / window,
        "tpot_p95_ms": None if not tpots else pct(tpots, P95) * 1e3,
        "n_tpot_requests": len(tpots),
        "n_tokens": n_tokens,
        "window_s": window,
        "attempted": sum(1 for s in served if s.sent < t1),
        "failed": sum(1 for s in served if s.sent < t1 and
                      (s.req.truncated or s.req.expired)),
    }


def window_work(served, t0: float, t1: float):
    """KV depth of every decode token in the window, and the prompt length
    of every prefill whose first token came in it."""
    decode_kv, prefill_lens = [], []
    for s in served:
        P = len(s.spec.prompt)
        for i, t in enumerate(s.token_times):
            if t0 <= t <= t1:
                if i == 0:
                    prefill_lens.append(P)
                else:
                    decode_kv.append(P + i)
    return {"decode_kv": np.asarray(decode_kv), "prefill_lens": prefill_lens}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------


def check_sample(served, t0: float, t1: float, n: int, rng) -> List:
    """Requests finished in the window: the one with the most served
    tokens, and ``n - 1`` more drawn from the seed."""
    done = [s for s in served if s.req.finish_time is not None
            and t0 <= s.req.finish_time <= t1 and s.req.generated]
    if not done:
        return []
    done.sort(key=lambda s: s.req.req_id)
    longest = max(done, key=lambda s: len(s.req.generated))
    rest = [s for s in done if s is not longest]
    pick = list(rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(ref, weights, dm, sample, row_len: int, control: Optional[str] = None):
    reqs = [(list(s.spec.prompt), list(s.req.generated)) for s in sample]
    return ref.served_gaps(weights, dm, reqs, row_len, control=control)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    dm: object
    peak: Dict
    window_s: float
    work: Dict
    spans: List[Dict] = field(default_factory=list)
    trace: object = None
    dev: int = 0
    trace_window_s: float = 0.0
    decode_counts: List = field(default_factory=list)
    decode_kv_lens: List = field(default_factory=list)


def run(argv=None, *, require_tpu: bool = True, root: Optional[Path] = None,
        fault=None, keep=None, control: Optional[str] = None) -> Dict:
    """One run; returns the result dict (also printed).  ``require_tpu``,
    ``fault`` (a callable given the engine before the window, to break the
    timed path), ``keep`` (a dict that receives the sample's gaps) and
    ``control`` (a precision mode whose gaps are read on the same sample and
    judged by the same limit, as ``control_correct``) exist for the
    benchmark's own tests and limit readings."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or Path.cwd()
    if not (root / "src" / "repro").is_dir():
        raise SystemExit(f"no program under test: {root / 'src' / 'repro'} is missing")
    sys.path.insert(0, str(root / "src"))
    cell = find_cell(root, args.workload)
    if cell.mix.get("loop") != "closed":
        raise SystemExit(f"traffic loop {cell.mix.get('loop')!r}: only a closed loop is served")

    import jax
    import jax.numpy as jnp

    devs = use_chip(cell.chips, require_tpu)
    use_compile_cache(root)
    # the CPU tests borrow the v5e's peaks; a chip's kind must be in the table
    peak = peak_entry(devs[0].device_kind if require_tpu else "TPU v5 lite")

    from benchmark import serve, weights as wmod
    from repro.models import LM
    from repro.telemetry import Telemetry

    cfg, mix = cell.config, cell.mix
    ref = reference_module(root, cfg)
    dm = ref.dims_from_config(cfg)
    arch = serve.build_arch(cfg)
    serve.check_arch(arch, dm)

    marks = [("start", T_START), ("imports", time.perf_counter())]
    abstract = LM(arch, dtype=getattr(jnp, cfg["program"]["dtype"])).abstract_params()
    weights, c_hat = wmod.make_weights(abstract, dm.d, args.seed)
    weights, shaping = wmod.shape_routing(ref, weights, c_hat, dm, mix["routing"], args.seed)
    jax.block_until_ready(weights)
    marks.append(("weights and routing", time.perf_counter()))
    print("routing shaping: gains " + " ".join(f"{g:.4g}" for g in shaping["gains"])
          + "; hot share target/calibration "
          + " ".join(f"{a:.3f}/{b:.3f}" for a, b in
                     zip(shaping["target_hot_share"], shaping["calibration_hot_share"]))
          + "; share of tokens with a negative projection on c "
          + " ".join(f"{x:.3f}" for x in shaping["negative_projection"]),
          file=sys.stderr)

    tel = Telemetry(capacity=1 << 20, enabled=bool(args.trace))
    engine = serve.build_engine(arch, cfg, weights, tel)
    tap = serve.CountTap(engine, kv_lens=bool(args.trace))
    marks.append(("engine", time.perf_counter()))
    serve.warm_up(engine, mix, dm.vocab)
    marks.append(("warm-up", time.perf_counter()))
    if fault is not None:
        fault(engine)
    tel.reset()

    rng = wmod.seed_rng(args.seed, 4)
    annotate = None
    trace_dir = None
    if args.trace:
        annotate = jax.profiler.TraceAnnotation
    loop = serve.Loop(engine, mix, dm.vocab, rng, annotate=annotate)
    state = {}

    def window_start():
        state["setup_s"] = time.perf_counter() - T_START
        marks.append(("pre-roll", time.perf_counter()))
        print("setup: " + ", ".join(f"{a} {t - marks[i][1]:.2f} s" for i, (a, t)
                                    in enumerate(marks[1:])), file=sys.stderr)
        tap.recording = True
        if args.trace:
            nonlocal trace_dir
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            state["trace_t0"] = time.perf_counter()

    def stop_trace():
        state["trace_window_s"] = time.perf_counter() - state["trace_t0"]
        state["traced_decodes"] = len(tap.counts)
        jax.profiler.stop_trace()

    def during(t_in):
        # between steps (each ends in a host copy of its logits, so the
        # device has finished): stop tracing after TRACE_SECONDS
        if args.trace and t_in is not None and "trace_window_s" not in state \
                and t_in >= min(TRACE_SECONDS, args.seconds):
            stop_trace()

    t0, t1 = loop.run(args.seconds, on_window_start=window_start, during=during)
    tap.recording = False
    if args.trace and "trace_window_s" not in state:
        jax.block_until_ready(engine.cache)
        stop_trace()
    n_window_decodes = len(tap.counts)
    served = list(loop.served.values())
    e2e = end_to_end(served, t0, t1)

    mem = devs[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    counts = [np.asarray(c) for c in tap.counts[:n_window_decodes]]
    hot = shaping["hot_masks"]
    if counts:
        tot = np.sum(counts, axis=0)  # (L, E)
        share = [float(tot[j][hot[j]].sum() / max(tot[j].sum(), 1)) for j in range(len(tot))]
        print("decode hot share by MoE layer (window, all decode rows): "
              + " ".join(f"{x:.3f}" for x in share), file=sys.stderr)

    # the model-FLOP share is read where the profiler is off: after the
    # traced seconds of a --trace 1 run, over the whole window otherwise
    t_mfu = t0 + state.get("trace_window_s", 0.0) if args.trace else t0
    if t1 - t_mfu < 1.0:
        t_mfu = t0
    ctx = Context(dm=dm, peak=peak, window_s=t1 - t_mfu,
                  work=window_work(served, t_mfu, t1))
    breakdown = None
    if args.trace:
        from benchmark import trace as trmod

        t_ns0, t_ns1 = int(t0 * 1e9), int(t1 * 1e9)
        ctx.spans = [e for e in tel.events() if e["kind"] == "span"
                     and t_ns0 <= e["t0_ns"] <= t_ns1]
        trace = trmod.load(trmod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = trace
        ctx.dev = trmod.first_device(trace) or 0
        ctx.trace_window_s = state["trace_window_s"]
        ctx.decode_counts = counts[:state["traced_decodes"]]
        ctx.decode_kv_lens = tap.kv_lens[:state["traced_decodes"]]
        ops = trace.ops.get(ctx.dev)
        if ops is not None and len(ops):
            busy = trmod.busy_ns(ops) * 1e-9
            breakdown = {"device_ops": trmod.top_ops(ops),
                         "idle_gaps": trmod.idle_gaps(ops, trace.host)}
            state["busy_s"] = busy

    # free the program's state before the reference runs
    stale = engine.cache
    tap.engine = None
    del engine, loop.engine, tap
    jax.tree.map(lambda a: a.delete() if hasattr(a, "delete") else None, stale)
    gc.collect()

    sample = check_sample(served, t0, t1, int(mix["check_requests"]), wmod.seed_rng(args.seed, 5))
    checks = {}
    correct = bool(sample)
    control_correct = None
    t_ref = time.perf_counter()
    if sample:
        gaps, cgaps, agree = compare(ref, weights, dm, sample, cfg["program"]["max_seq"],
                                     control=control)
        lim = cell.limits["mean_logit_gap"]
        mean = float(gaps.mean())
        checks["mean_logit_gap"] = {"value": mean, "limit": lim}
        correct = mean <= lim
        if cgaps is not None:
            # the control in the program's place, held to the same limit
            cmean = float(cgaps.mean())
            checks["control_mean_logit_gap"] = {"value": cmean, "limit": lim}
            control_correct = cmean <= lim
        print("gap statistics: " + json.dumps(gap_stats(gaps)), file=sys.stderr)
        print(f"reference: {len(sample)} requests, {gaps.size} served tokens, "
              f"argmax agreement {agree:.4f}, {time.perf_counter() - t_ref:.1f} s",
              file=sys.stderr)
        if keep is not None:
            keep.update(gaps=gaps, control_gaps=cgaps, agreement=agree)
    else:
        print("check mean_logit_gap: no request finished in the window", file=sys.stderr)

    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            v = metric_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e["setup_s"] = state["setup_s"]
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if args.trace:
        device["busy_s"] = state.get("busy_s", 0.0)
        device["window_s"] = ctx.trace_window_s
    result = {"correct": bool(correct), "attempted": int(e2e["attempted"]),
              "failed": int(e2e["failed"]), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control is not None:
        result["control_correct"] = control_correct
    result["checks"] = checks
    print(f"window {t1 - t0:.3f} s, {e2e['n_tokens']} output tokens, "
          f"{e2e['n_tpot_requests']} requests with a TPOT, setup "
          f"{state['setup_s']:.3f} s", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return result


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
