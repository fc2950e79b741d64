"""The system under test, driven through its serving entry point.

Builds the program's ``ServingEngine`` (``repro.launch.serve.build_engine``,
``policy="sieve"``, its default cost source) on the benchmark's weights,
warms every shape the cell's traffic uses, and runs the closed loop, recording the host time at which each output token reached the
caller (the return of the ``engine.step`` that produced it).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import traffic as tr


@dataclass
class Served:
    """One request as the client saw it."""

    spec: tr.Spec
    req: object  # the program's Request
    sent: float  # host time it was sent
    token_times: List[float] = field(default_factory=list)


def build_arch(cfg: Dict):
    """The program's ArchConfig for the configuration: its repo arch with
    the file's overrides (top level, ``moe``, ``attn`` and ``mla``)."""
    from repro.configs import get_arch

    prog = cfg["program"]
    arch = get_arch(cfg["repo_arch"])
    attn = dataclasses.replace(arch.attn, **prog.get("attn_overrides", {}))
    if attn.mla is not None:
        attn = dataclasses.replace(
            attn, mla=dataclasses.replace(attn.mla, **prog.get("mla_overrides", {})))
    return dataclasses.replace(
        arch, attn=attn,
        moe=dataclasses.replace(arch.moe, **prog.get("moe_overrides", {})),
        **prog.get("overrides", {}),
    )


def check_arch(arch, dm) -> None:
    """The program's sizes must be the configuration file's."""
    a, m = arch.attn, arch.moe
    pairs = [("d_model", arch.d_model, dm.d), ("vocab", arch.vocab_size, dm.vocab),
             ("layers", arch.n_layers, dm.n_layers), ("heads", a.n_heads, dm.n_heads),
             ("experts", m.n_experts, dm.n_experts), ("top_k", m.top_k, dm.top_k),
             ("d_expert", m.d_expert, dm.d_expert), ("shared", m.n_shared, dm.n_shared),
             ("dense lead", m.first_k_dense, dm.n_dense_lead),
             ("attention", a.kind, dm.attn), ("rope theta", a.rope_theta, dm.rope_theta)]
    if dm.attn == "gqa":
        pairs += [("kv heads", a.n_kv_heads, dm.n_kv_heads), ("head dim", a.d_head, dm.head_dim)]
    else:
        pairs += [("q_lora", a.mla.q_lora_rank, dm.q_lora), ("kv_lora", a.mla.kv_lora_rank, dm.kv_lora),
                  ("qk_nope", a.mla.qk_nope_dim, dm.qk_nope), ("qk_rope", a.mla.qk_rope_dim, dm.qk_rope),
                  ("v_head", a.mla.v_head_dim, dm.v_head)]
    if dm.n_dense_lead:
        pairs.append(("dense d_ff", arch.d_ff, dm.d_ff_dense))
    bad = [(k, x, y) for k, x, y in pairs if x != y]
    if bad:
        raise SystemExit(f"program arch differs from the configuration file: {bad}")


def build_engine(arch, cfg: Dict, weights, telemetry):
    import jax.numpy as jnp
    from repro.launch.serve import build_engine as program_build_engine
    from repro.serving import BatchingConfig

    prog = cfg["program"]
    batching = BatchingConfig(n_slots=prog["n_slots"], max_seq=prog["max_seq"])
    return program_build_engine(
        arch, batching, dtype=getattr(jnp, prog["dtype"]), params=weights,
        telemetry=telemetry,
    )


class CountTap:
    """Keeps the per-layer expert counts (``aux.counts``) that each decode
    step already returns, without adding a sync: wraps the engine's
    compiled decode call.  With ``kv_lens`` set it also notes the live
    rows' KV lengths."""

    def __init__(self, engine, kv_lens: bool = False):
        self.inner = engine._decode
        self.engine = engine
        self.counts: List = []
        self.kv_lens: Optional[List] = [] if kv_lens else None
        self.recording = False
        engine._decode = self

    def __call__(self, params, batch, cache):
        if self.recording and self.kv_lens is not None:
            self.kv_lens.append([r.position for r in self.engine.sched.decode_batch()])
        out = self.inner(params, batch, cache)
        if self.recording:
            self.counts.append(out[2].counts)
        return out

    def __getattr__(self, name):  # _cache_size and friends
        return getattr(self.inner, name)


def warm_up(engine, mix: Dict, vocab: int) -> None:
    """Compile (or load from the cache) every shape the traffic uses: one
    prefill per prompt bucket and the decode step."""
    from repro.serving import Request

    rng = np.random.default_rng(0)
    for p in tr.bucket_sizes(mix):
        engine.submit(Request(prompt=list(rng.integers(0, vocab, p)), max_new_tokens=2))
    engine.run_until_done()
    engine.sched.finished.clear()


class Loop:
    """Drives the engine for one run in a closed loop: pre-roll, then the
    window."""

    def __init__(self, engine, mix: Dict, vocab: int, seed_rng, annotate=None):
        self.engine = engine
        self.mix = mix
        self.pop = tr.Population(mix, vocab, seed_rng)
        self.rng = seed_rng
        self.served: Dict[int, Served] = {}
        self.annotate = annotate
        self._seen: Dict[int, int] = {}

    def _ann(self, name):
        if self.annotate is None:
            return _NULL
        return self.annotate(name)

    def _send(self, spec: tr.Spec, due: float) -> None:
        from repro.serving import Request

        req = Request(prompt=spec.prompt.tolist(), max_new_tokens=spec.max_new)
        self.engine.submit(req)
        self.served[req.req_id] = Served(spec, req, due)
        self._seen[req.req_id] = 0

    def _record(self, t: float) -> None:
        for r in list(self.engine.sched.active) + self._just_done:
            s = self.served.get(r.req_id)
            if s is None:
                continue
            n = len(r.generated)
            if n > self._seen[r.req_id]:
                s.token_times.extend([t] * (n - self._seen[r.req_id]))
                self._seen[r.req_id] = n

    def _step(self):
        with self._ann("bench/engine_step"):
            done = self.engine.step()
        t = time.perf_counter()
        self._just_done = done
        self._record(t)
        return done, t

    def run(self, seconds: float, on_window_start=None, during=None):
        """Pre-roll, then measure for ``seconds``; returns (t0, t1)."""
        self._just_done = []
        start = time.perf_counter()
        for spec in tr.first_requests(self.pop, self.engine.cfg.n_slots, self.rng):
            self._send(spec, start)
        t0 = start + float(self.mix.get("preroll_s", 0.0))
        t1 = None
        while True:
            now = time.perf_counter()
            if t1 is None and now >= t0:
                t0, t1 = now, now + seconds
                if on_window_start:
                    on_window_start()
            if t1 is not None and now >= t1:
                break
            if during:
                during(None if t1 is None else now - t0)
            done, t = self._step()
            with self._ann("bench/generate"):
                for r in done:
                    self._send(self.pop.next(), t)
        return t0, time.perf_counter()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()
