"""repro.telemetry: ring/span core, disabled-mode no-op guarantees,
Perfetto export, the measured cost loop (TimingFeed + StageProbes), and
the serving/cluster integrations.

The two contracts that keep telemetry shippable:

* **off = free**: disabled telemetry allocates nothing on the hot path
  and an engine with telemetry off generates bit-identical tokens to one
  with telemetry on;
* **measured = closed loop**: under ``cost_source="measured"`` the cost
  table is fed exclusively from span-measured stage probes — the DRAM
  proxy is never consulted — and the resulting in-graph splits stay
  inside the dual-path feasibility window without recompiling decode.
"""

import dataclasses
import gc
import json
import tracemalloc

import numpy as np
import pytest

from repro.core.cost_model import CostModel, MoELayerSpec, b200_pim_system
from repro.core.cost_table import CostTable
from repro.telemetry import (
    NULL_SPAN,
    StageProbes,
    Telemetry,
    TimingFeed,
    trace_events,
    write_trace,
)
from repro.telemetry.core import _Hist
from repro.telemetry.probes import TAIL_SPAN


# ---------------------------------------------------------------------------
# Core: ring, spans, aggregates
# ---------------------------------------------------------------------------


class TestTelemetryCore:
    def test_span_records_into_ring(self):
        t = [0]
        tel = Telemetry(enabled=True, clock=lambda: t[0])
        with tel.span("work", value=7.0):
            t[0] = 1500
        (ev,), cur = tel.events_since(0)
        assert cur == 1
        assert ev["kind"] == "span" and ev["name"] == "work"
        assert ev["t0_ns"] == 0 and ev["dur_ns"] == 1500
        assert ev["value"] == 7.0
        assert ev["track"] == "main"

    def test_ring_wraparound_keeps_most_recent(self):
        tel = Telemetry(capacity=8, enabled=True)
        for i in range(20):
            tel.point("p", float(i))
        assert tel.n_events == 8
        assert tel.n_emitted == 20
        assert tel.n_overflowed == 12
        vals = [e["value"] for e in tel.events()]
        assert vals == [float(i) for i in range(12, 20)]

    def test_events_since_cursor_is_monotone(self):
        tel = Telemetry(capacity=16, enabled=True)
        tel.point("a", 1.0)
        evs, cur = tel.events_since(0)
        assert len(evs) == 1
        evs, cur2 = tel.events_since(cur)
        assert evs == [] and cur2 == cur
        tel.point("a", 2.0)
        evs, _ = tel.events_since(cur)
        assert [e["value"] for e in evs] == [2.0]

    def test_events_since_skips_wrapped_events(self):
        tel = Telemetry(capacity=4, enabled=True)
        tel.point("a", 0.0)
        _, cur = tel.events_since(0)
        for i in range(10):  # overwrite everything the cursor points at
            tel.point("a", float(i + 1))
        evs, _ = tel.events_since(cur)
        assert [e["value"] for e in evs] == [7.0, 8.0, 9.0, 10.0]

    def test_tracks_and_span_at_simulated_time(self):
        tel = Telemetry(enabled=True)
        tel.span_at("step", 1.5, 0.25, track="replica-1", value=2.0)
        (ev,) = tel.events()
        assert ev["track"] == "replica-1"
        assert ev["t0_ns"] == int(1.5e9) and ev["dur_ns"] == int(0.25e9)
        assert tel.tracks == ["main", "replica-1"]

    def test_counters_and_gauges_aggregate_and_sample(self):
        tel = Telemetry(enabled=True)
        tel.counter("hits", 2)
        tel.counter("hits", 3)
        tel.gauge("occ", 0.5)
        tel.gauge("occ", 0.75)
        assert tel.counters() == {"hits": 5.0}
        assert tel.gauges() == {"occ": 0.75}
        # each update also dropped a ring sample (counter: cumulative)
        vals = [e["value"] for e in tel.events() if e["name"] == "hits"]
        assert vals == [2.0, 5.0]

    def test_reset_clears_events_and_aggregates(self):
        tel = Telemetry(enabled=True)
        tel.counter("c")
        tel.observe("h", [1, 2])
        tel.reset()
        assert tel.n_events == 0
        assert tel.counters() == {} and "h" not in tel.snapshot()

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            Telemetry(capacity=0)

    def test_histogram_bucketing_pow2_le_semantics(self):
        h = _Hist()
        h.observe_many(np.array([0.5, 1.0, 2.0, 3.0, 2.0**20, 2.0**20 + 1]))
        # le=1 catches 0.5 and 1.0; le=2 catches 2.0; le=4 catches 3.0;
        # the last finite bucket catches 2**20; +Inf catches the rest
        assert h.buckets[0] == 2
        assert h.buckets[1] == 1
        assert h.buckets[2] == 1
        assert h.buckets[h.N_BUCKETS - 2] == 1
        assert h.buckets[h.N_BUCKETS - 1] == 1
        assert h.count == 6 and h.vmax == 2.0**20 + 1

    def test_prometheus_snapshot_schema(self):
        tel = Telemetry(enabled=True)
        tel.counter("engine/jit_cache_miss", 3)
        tel.gauge("head_mass/layer0", 0.9)
        tel.observe("expert_tokens/layer0", [1, 1, 5])
        text = tel.snapshot()
        assert "# TYPE repro_engine_jit_cache_miss counter" in text
        assert "repro_engine_jit_cache_miss 3" in text
        assert "# TYPE repro_head_mass_layer0 gauge" in text
        assert "repro_head_mass_layer0 0.9" in text
        assert "# TYPE repro_expert_tokens_layer0 histogram" in text
        # cumulative buckets, closed by +Inf == _count
        assert 'repro_expert_tokens_layer0_bucket{le="1"} 2' in text
        assert 'repro_expert_tokens_layer0_bucket{le="+Inf"} 3' in text
        assert "repro_expert_tokens_layer0_sum 7" in text
        assert "repro_expert_tokens_layer0_count 3" in text


# ---------------------------------------------------------------------------
# Disabled mode: the no-op guarantees
# ---------------------------------------------------------------------------


class TestDisabledMode:
    def test_disabled_span_is_shared_singleton(self):
        tel = Telemetry(enabled=False)
        assert tel.span("a") is NULL_SPAN
        assert tel.span("b", value=1.0, track="t") is NULL_SPAN

    def test_disabled_records_nothing(self):
        tel = Telemetry(enabled=False)
        with tel.span("x"):
            pass
        tel.span_at("y", 0.0, 1.0)
        tel.point("p", 1.0)
        tel.counter("c")
        tel.gauge("g", 1.0)
        tel.observe("h", [1, 2, 3])
        assert tel.n_events == 0 and tel.n_emitted == 0
        assert tel.counters() == {} and tel.gauges() == {}
        assert tel.snapshot() == ""

    def test_disabled_hot_path_allocates_nothing(self):
        """tracemalloc sees zero allocations attributed to telemetry/core
        across a burst of disabled-mode calls (the compiled-out posture)."""
        from repro.telemetry import core as core_mod

        tel = Telemetry(enabled=False)
        vals = [1, 2, 3]

        def burst():
            for _ in range(200):
                with tel.span("hot", value=1.0):
                    pass
                tel.counter("c")
                tel.gauge("g", 0.5)
                tel.observe("h", vals)
                tel.point("p", 1.0)

        burst()  # warm any lazy interpreter state
        # Measure telemetry's allocations, not the interpreter's: cyclic-GC
        # passes and eval-breaker pending calls (e.g. runtimes deferring
        # object destruction to the main thread) can fire mid-burst and get
        # attributed to whatever core.py line is current.  Those are
        # asynchronous one-offs — a real allocation in the disabled path
        # would show up on *every* burst — so require one clean burst out
        # of a few attempts.
        for _ in range(4):
            gc.collect()
            gc_was_enabled = gc.isenabled()
            gc.disable()
            tracemalloc.start()
            try:
                burst()
                snap = tracemalloc.take_snapshot()
            finally:
                tracemalloc.stop()
                if gc_was_enabled:
                    gc.enable()
            stats = snap.filter_traces(
                [tracemalloc.Filter(True, core_mod.__file__)]
            ).statistics("lineno")
            if sum(s.size for s in stats) == 0:
                break
        else:
            assert False, stats


# ---------------------------------------------------------------------------
# Perfetto / Chrome trace export
# ---------------------------------------------------------------------------


class TestTraceExport:
    def _session(self):
        tel = Telemetry(enabled=True)
        tel.span_at("replica/step", 0.0, 0.5, track="replica-0", value=3.0)
        tel.span_at("replica/step", 0.1, 0.4, track="replica-1")
        tel.point("queue_depth", 2.0, t_s=0.2, track="replica-0")
        return tel

    def test_trace_event_schema(self):
        evs = trace_events(self._session())
        meta = [e for e in evs if e["ph"] == "M"]
        spans = [e for e in evs if e["ph"] == "X"]
        points = [e for e in evs if e["ph"] == "C"]
        assert {m["args"]["name"] for m in meta} == {
            "main", "replica-0", "replica-1"
        }
        assert len(spans) == 2 and len(points) == 1
        s0 = next(s for s in spans if "args" in s)
        assert s0["ts"] == 0.0 and s0["dur"] == pytest.approx(0.5e6)
        assert s0["args"]["value"] == 3.0
        # NaN-valued span carries no args (NaN is not valid JSON)
        s1 = next(s for s in spans if "args" not in s)
        assert s1["dur"] == pytest.approx(0.4e6)
        assert points[0]["args"]["value"] == 2.0
        # spans map onto their track's pid
        pid_of = {m["args"]["name"]: m["pid"] for m in meta}
        assert s0["pid"] == pid_of["replica-0"]

    def test_write_trace_is_valid_json(self, tmp_path):
        path = write_trace(self._session(), str(tmp_path / "t" / "x.json"))
        with open(path) as f:
            doc = json.load(f)  # also proves no NaN leaked into the JSON
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"]["producer"] == "repro.telemetry"
        assert doc["otherData"]["n_overflowed"] == 0
        assert len(doc["traceEvents"]) == 6  # 3 metadata + 2 spans + 1 point

    def test_trace_report_summarizes(self, tmp_path):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "make_trace_report",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", "make_trace_report.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        path = write_trace(self._session(), str(tmp_path / "x.json"))
        summary = mod.main([path, "--json"])
        st = summary["spans"]["replica/step"]
        assert st["count"] == 2
        assert st["p50_us"] == pytest.approx(0.4e6)
        assert st["p99_us"] == pytest.approx(0.5e6)
        assert summary["counters"]["queue_depth"] == 2.0


# ---------------------------------------------------------------------------
# TimingFeed: measured spans -> CostTable
# ---------------------------------------------------------------------------


def _layer():
    return MoELayerSpec(d_model=64, d_ff=32, n_experts=8, top_k=2)


class TestTimingFeed:
    def test_round_trip_into_cost_table(self):
        tel = Telemetry(enabled=True)
        table = CostTable(fallback=lambda n: 1.0)  # fallback to expose misses
        feed = TimingFeed(table, tel)
        tel.span_at(TAIL_SPAN, 0.0, 3e-5, value=2.0)
        tel.span_at(TAIL_SPAN, 0.1, 5e-5, value=4.0)
        fed = feed.poll()
        assert fed == {2: pytest.approx(3e-5), 4: pytest.approx(5e-5)}
        # first observation replaces the fallback outright
        assert table.lookup(2) == pytest.approx(3e-5)
        assert table.lookup(4) == pytest.approx(5e-5)
        assert feed.n_polls == 1 and feed.n_fed == 2

    def test_poll_is_incremental_and_means_duplicates(self):
        tel = Telemetry(enabled=True)
        table = CostTable(fallback=lambda n: 1.0)
        feed = TimingFeed(table, tel)
        tel.span_at(TAIL_SPAN, 0.0, 2e-5, value=3.0)
        tel.span_at(TAIL_SPAN, 0.1, 4e-5, value=3.0)
        fed = feed.poll()
        assert fed[3] == pytest.approx(3e-5)  # in-window mean
        assert feed.poll() == {}  # nothing new -> no table touch
        v0 = table.version
        feed.poll()
        assert table.version == v0

    def test_ignores_other_spans_and_invalid_values(self):
        tel = Telemetry(enabled=True)
        table = CostTable(fallback=lambda n: 1.0)
        feed = TimingFeed(table, tel)
        tel.span_at("engine/step", 0.0, 1e-3)  # wrong name
        tel.span_at(TAIL_SPAN, 0.0, 1e-3)  # NaN value (no token count)
        tel.span_at(TAIL_SPAN, 0.0, 1e-3, value=0.0)  # count < 1
        tel.point(TAIL_SPAN, 5.0)  # a point, not a span
        assert feed.poll() == {}

    def test_ema_convergence_on_skewed_trace(self):
        """Repeated measured windows converge the EMA onto the true stage
        time for every count in a skewed (bimodal) count distribution."""
        rng = np.random.default_rng(0)
        tel = Telemetry(enabled=True)
        table = CostTable(fallback=lambda n: 1.0, alpha=0.5)
        feed = TimingFeed(table, tel)
        true_t = {1: 1e-5, 2: 1.8e-5, 16: 9e-5}  # head-heavy: mostly 1s
        t = 0.0
        for _ in range(40):
            for count, base in true_t.items():
                dur = base * (1.0 + rng.normal(0.0, 0.02))
                tel.span_at(TAIL_SPAN, t, dur, value=float(count))
                t += dur
            feed.poll()
        for count, base in true_t.items():
            assert table.lookup(count) == pytest.approx(base, rel=0.05)


# ---------------------------------------------------------------------------
# StageProbes: timed decode-stage cells
# ---------------------------------------------------------------------------


class TestStageProbes:
    @pytest.fixture(scope="class")
    def probes(self):
        tel = Telemetry(enabled=True)
        return StageProbes(
            d_model=32, d_expert=16, telemetry=tel, attn_dims=(4, 2, 8)
        )

    def test_tail_probe_emits_count_keyed_span(self, probes):
        dt = probes.tail(3)
        assert dt > 0
        evs = [e for e in probes.tel.events() if e["name"] == TAIL_SPAN]
        assert evs and evs[-1]["value"] == 3.0
        assert evs[-1]["dur_ns"] > 0

    def test_probe_jits_are_memoized(self, probes):
        probes.tail(3)
        n = len(probes._jits)
        probes.tail(3)  # same shape -> no new compile
        assert len(probes._jits) == n

    def test_head_dispatch_attention_probes_run(self, probes):
        assert probes.head([5, 3, 1]) > 0
        assert probes.dispatch(8, n_experts=8, top_k=2) > 0
        assert probes.attention(4, 100) > 0
        names = {e["name"] for e in probes.tel.events()}
        assert {
            "stage/head_gmm", "stage/dispatch", "stage/attention"
        } <= names

    def test_attention_probe_without_dims_is_noop(self):
        tel = Telemetry(enabled=True)
        p = StageProbes(d_model=16, d_expert=8, telemetry=tel)
        assert p.attention(2, 10) == 0.0
        assert tel.n_events == 0

    def test_feed_round_trip_through_real_probe(self, probes):
        """Probe -> span -> TimingFeed -> CostTable: the measured loop's
        data path, end to end on a real timed execution."""
        table = CostTable(fallback=lambda n: 1.0)
        feed = TimingFeed(table, probes.tel)
        probes.tail(5)
        fed = feed.poll()
        assert 5 in fed and 0.0 < fed[5] < 1.0
        assert table.lookup(5) == pytest.approx(fed[5])


# ---------------------------------------------------------------------------
# Measured split decisions: feasibility + convergence (no engine needed)
# ---------------------------------------------------------------------------


class TestMeasuredSplitDecisions:
    def test_measured_fed_split_stays_in_feasibility_window(self):
        """SieveStates exported from a measured-fed table keep the
        in-graph split inside [n_over, max_head] for any measured costs
        (here: adversarially slow tails), on a skewed count vector."""
        import jax.numpy as jnp

        from repro.core.scheduler_jax import (
            dual_path_split_cost,
            make_sieve_state,
        )

        cm = CostModel(system=b200_pim_system(), layer=_layer())
        tel = Telemetry(enabled=True)
        table = CostTable(fallback=cm.t_pim_gemv_roofline)
        feed = TimingFeed(table, tel)
        # adversarial measurement: tail path is terrible at every count
        for i, c in enumerate((1, 2, 4, 8)):
            tel.span_at(TAIL_SPAN, 0.01 * i, 5e-2, value=float(c))
        feed.poll()
        state = make_sieve_state(table, cm, 16, total_routed_tokens=16)
        rows = jnp.asarray([8, 4, 2, 1, 1, 0, 0, 0], jnp.int32)
        tail_tokens, max_head = 2, 4
        out = dual_path_split_cost(
            rows,
            jnp.asarray(state.pim_time_by_count),
            jnp.asarray(state.params),
            tail_tokens=tail_tokens,
            max_head=max_head,
        )
        n_head = int(out["n_head"])
        n_over = int((rows > tail_tokens).sum())
        assert n_over <= n_head <= max_head

    def test_measured_costs_steer_the_split(self):
        """Cheap measured tails pull experts onto the tail path; slow
        measured tails push the split toward the head — the closed loop
        actually reacts to measurements."""
        import jax.numpy as jnp

        from repro.core.scheduler_jax import (
            dual_path_split_cost,
            make_sieve_state,
        )

        cm = CostModel(system=b200_pim_system(), layer=_layer())
        rows = jnp.asarray([8, 6, 4, 2, 1, 1, 0, 0], jnp.int32)

        def split_with_tail_cost(per_token_s):
            tel = Telemetry(enabled=True)
            table = CostTable(fallback=cm.t_pim_gemv_roofline)
            feed = TimingFeed(table, tel)
            for i, c in enumerate((1, 2, 4, 6, 8)):
                tel.span_at(
                    TAIL_SPAN, 0.01 * i, per_token_s * c, value=float(c)
                )
            feed.poll()
            state = make_sieve_state(table, cm, 16, total_routed_tokens=16)
            out = dual_path_split_cost(
                rows,
                jnp.asarray(state.pim_time_by_count),
                jnp.asarray(state.params),
                tail_tokens=8,
                max_head=8,
            )
            return int(out["n_head"])

        assert split_with_tail_cost(1e-9) <= split_with_tail_cost(1e-2)


# ---------------------------------------------------------------------------
# Serving-engine integration
# ---------------------------------------------------------------------------


def _moe_engine(telemetry=None, cost_source="model", expert_exec="dual_path",
                policy="sieve", n_slots=4, refresh=4):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import LM
    from repro.serving import BatchingConfig, ServingEngine

    arch = get_arch("qwen3-moe-30b-a3b").reduced()
    arch = dataclasses.replace(
        arch, moe=dataclasses.replace(arch.moe, expert_exec=expert_exec)
    )
    lm = LM(arch, dtype=jnp.float32)
    p = lm.init(jax.random.PRNGKey(0))
    return ServingEngine(
        lm, p, BatchingConfig(n_slots=n_slots, max_seq=64),
        policy=policy, telemetry=telemetry, cost_source=cost_source,
        sieve_refresh_every=refresh,
    )


def _run_requests(eng, n=4, prompt_len=8, max_new=6, seed=0):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.submit(Request(
            prompt=list(rng.integers(1, 255, size=prompt_len)),
            max_new_tokens=max_new,
        ))
    return eng.run_until_done()


class TestEngineTelemetry:
    def test_invalid_cost_source_rejected(self):
        with pytest.raises(ValueError, match="cost_source"):
            _moe_engine(cost_source="magic")

    def test_measured_requires_moe(self):
        import jax
        import jax.numpy as jnp

        from repro.configs import get_arch
        from repro.models import LM
        from repro.serving import BatchingConfig, ServingEngine

        arch = get_arch("granite-3-2b").reduced()
        lm = LM(arch, dtype=jnp.float32)
        p = lm.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="measured"):
            ServingEngine(
                lm, p, BatchingConfig(n_slots=2, max_seq=64),
                cost_source="measured",
            )

    def test_decode_bit_identical_telemetry_on_vs_off(self):
        outs = []
        for tel in (Telemetry(enabled=False), Telemetry(enabled=True)):
            eng = _moe_engine(telemetry=tel)
            done = _run_requests(eng)
            outs.append([r.generated for r in done])
        assert outs[0] == outs[1]

    def test_engine_emits_spans_and_metrics(self):
        tel = Telemetry(enabled=True)
        eng = _moe_engine(telemetry=tel)
        _run_requests(eng)
        names = {e["name"] for e in tel.events()}
        assert {"engine/step", "engine/admit", "engine/prefill",
                "engine/decode", "engine/sieve_host"} <= names
        gauges = tel.gauges()
        assert 0.0 <= gauges["engine/kv_occupancy"] <= 1.0
        assert 0.0 <= gauges["engine/batch_occupancy"] <= 1.0
        assert gauges["engine/drop_rate"] == eng.stats.drop_rate
        # per-layer expert histograms + head-mass bimodality gauges
        assert eng._layer_metric_names  # sieve pass saw >= 1 MoE layer
        snap = tel.snapshot()
        for hist_name, mass_name in eng._layer_metric_names:
            assert "repro_" + hist_name.replace("/", "_") in snap
            assert 0.0 <= gauges[mass_name] <= 1.0
        # the miss counter counts the programs built during the steps, one
        # engine/compile/* span each: here the decode step and the one
        # prompt length's prefill, every jit-cache entry the engine made
        compiles = [e for e in tel.events() if e["kind"] == "span"
                    and e["name"].startswith("engine/compile/")]
        assert tel.counters()["engine/jit_cache_miss"] == float(len(compiles))
        n_entries = (
            eng._decode._cache_size() + eng._prefill_chunk._cache_size()
        )
        assert len(compiles) == n_entries == 2
        assert eng._decode._cache_size() == 1

    def test_decode_step_host_tail_spans(self):
        """The decode step's host work is split into spans inside
        engine/step; the launch, wait and logits copy sit inside
        engine/decode, whose boundaries are unchanged."""
        tel = Telemetry(enabled=True)
        eng = _moe_engine(telemetry=tel)
        _run_requests(eng, n=2, max_new=4)
        spans = [e for e in tel.events() if e["kind"] == "span"]

        def of(name):
            return [e for e in spans if e["name"] == name]

        def within(inner, outer):
            return all(
                any(o["t0_ns"] <= i["t0_ns"]
                    and i["t0_ns"] + i["dur_ns"] <= o["t0_ns"] + o["dur_ns"]
                    for o in outer)
                for i in inner
            )

        n_decode = len(of("engine/decode"))
        assert n_decode > 0
        for name in ("engine/decode_launch", "engine/decode_wait",
                     "engine/decode_logits"):
            assert len(of(name)) == n_decode
            assert within(of(name), of("engine/decode"))
        for name in ("engine/decode_sample", "engine/aux_to_host",
                     "engine/sieve_host", "engine/retire", "engine/telemetry"):
            assert of(name) and within(of(name), of("engine/step"))
            assert not within(of(name), of("engine/decode"))
        # the sample span carries the rows it sampled (every slot's)
        assert {e["value"] for e in of("engine/decode_sample")} == {4.0}
        assert len(of("engine/retire")) == len(of("engine/step"))

    def test_moe_layers_in_place_gauge(self, monkeypatch):
        """Recorded once at build time: every MoE layer reads the stacked
        expert weights in place on the Pallas path, none on the XLA path."""
        for backend, expected in (("pallas", 2.0), ("xla", 0.0)):
            monkeypatch.setenv("REPRO_DUAL_BACKEND", backend)
            tel = Telemetry(enabled=True)
            eng = _moe_engine(telemetry=tel)
            assert eng.lm.arch.n_layers == 2
            assert tel.gauges()["engine/moe_layers_in_place"] == expected
            # the other build-time gauge: every expert is held here
            assert tel.gauges()["engine/moe_experts_held"] == float(
                eng.lm.arch.moe.n_experts
            )
            assert [e["name"] for e in tel.events()] == [
                "engine/moe_layers_in_place", "engine/moe_experts_held"
            ]

    def test_engine_off_telemetry_records_nothing(self):
        tel = Telemetry(enabled=False)
        eng = _moe_engine(telemetry=tel)
        _run_requests(eng)
        assert tel.n_emitted == 0

    def test_measured_engine_never_touches_dram_proxy(self, monkeypatch):
        """Under cost_source='measured' the refresh path must not consult
        PimGemvModel — probe-measured spans are the only feed."""
        from repro.sim.dram import PimGemvModel

        def _boom(self, layer, n):
            raise AssertionError(
                "DRAM proxy consulted under cost_source='measured'"
            )

        monkeypatch.setattr(PimGemvModel, "expert_time", _boom)
        tel = Telemetry(enabled=True)
        eng = _moe_engine(
            telemetry=tel, cost_source="measured",
            expert_exec="dual_path_cost", policy="dual_cost",
        )
        _run_requests(eng, max_new=10)
        # the measured loop actually fed the table from probe spans
        assert eng._timing_feed.n_fed > 0
        assert eng._probes.n_probes > 0
        assert "stage/tail_gemv" in {e["name"] for e in tel.events()}
        # table refreshed past the initial export at least once
        assert len(eng.sieve_refreshes) >= 2
        # and the closed loop never retraced the compiled decode step
        assert eng._decode._cache_size() == 1

    def test_measured_engine_creates_private_telemetry_when_disabled(self):
        eng = _moe_engine(
            telemetry=Telemetry(enabled=False), cost_source="measured",
            expert_exec="dual_path_cost", policy="dual_cost",
        )
        assert eng.tel.enabled  # swapped in a live private instance
        _run_requests(eng, n=2, max_new=6)
        assert eng._timing_feed.n_fed > 0

    def test_model_cost_source_still_uses_proxy(self):
        eng = _moe_engine()  # cost_source="model"
        _run_requests(eng, n=2, max_new=6)
        assert eng._probes is None and eng._timing_feed is None
        assert eng.cost_table.version > 0  # proxy observations landed


# ---------------------------------------------------------------------------
# Bridge to JAX: profiler annotations and the compile record
# ---------------------------------------------------------------------------


def _compile_spans(tel):
    return [e for e in tel.events() if e["kind"] == "span"
            and e["name"].startswith("engine/compile/")]


class TestProfilerBridge:
    def test_engine_spans_on_the_profiler_host_plane(self, tmp_path):
        """Under a profiler session every engine/* span is also a host
        event of the .xplane.pb, nested in engine/step, on the clock of
        the XLA operations the same trace records."""
        import glob

        import jax
        from jax.profiler import ProfileData

        eng = _moe_engine(telemetry=Telemetry(enabled=True))
        _run_requests(eng, n=2, max_new=3)  # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _run_requests(eng, n=2, max_new=3)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        host, xla = {}, []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith("engine/"):
                        host.setdefault(e.name, []).append(iv)
                    elif line.name.startswith("tf_XLA"):
                        xla.append(iv)
        steps = host["engine/step"]
        for name in ("engine/step", "engine/decode", "engine/decode_launch",
                     "engine/decode_wait", "engine/decode_logits",
                     "engine/decode_sample", "engine/sieve_host"):
            assert host.get(name), name
            for a, b in host[name]:
                assert any(s <= a and b <= e for s, e in steps), name
        # one clock: the decode program's operations run inside the
        # engine/decode spans that launched and waited for them
        decodes = host["engine/decode"]
        assert any(s <= a and b <= e for a, b in xla for s, e in decodes)

    def test_one_listener_per_process(self):
        from jax._src import monitoring as jmon

        from repro.telemetry import core as core_mod

        tels = [Telemetry(enabled=True) for _ in range(20)]
        tels += [Telemetry(enabled=False) for _ in range(5)]
        listeners = jmon.get_event_duration_listeners()
        assert listeners.count(core_mod._on_jax_event) == 1

    def test_compiles_recorded_into_enabled_instances_with_open_spans(self):
        import jax
        import jax.numpy as jnp

        a, b = Telemetry(enabled=True), Telemetry(enabled=True)
        idle, off = Telemetry(enabled=True), Telemetry(enabled=False)
        x = jnp.ones((3, 5))
        with a.span("outer"), b.span("outer"), off.span("outer"):
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        for tel in (a, b):
            (ev,) = _compile_spans(tel)
            assert ev["name"] == "engine/compile/jit(<lambda>)"
            (outer,) = [e for e in tel.events() if e["name"] == "outer"]
            assert outer["t0_ns"] <= ev["t0_ns"]
            assert ev["t0_ns"] + ev["dur_ns"] <= outer["t0_ns"] + outer["dur_ns"]
            assert tel.counters()["engine/jit_cache_miss"] == 1.0
        # no span open: not serving, so nothing recorded
        assert _compile_spans(idle) == [] and idle.n_emitted == 0
        assert off.n_emitted == 0

    def test_new_prompt_length_compiles_once_steady_decode_never(self):
        tel = Telemetry(enabled=True)
        eng = _moe_engine(telemetry=tel)
        _run_requests(eng)  # builds the prefill of 8 tokens and the decode
        tel.reset()
        _run_requests(eng)
        assert _compile_spans(tel) == []
        assert "engine/jit_cache_miss" not in tel.counters()
        tel.reset()
        n_prefill = eng._prefill_chunk._cache_size()
        _run_requests(eng, prompt_len=12)  # a prompt length not built yet
        spans = _compile_spans(tel)
        assert [e["name"] for e in spans] == ["engine/compile/jit(_prefill_chunk_impl)"]
        assert eng._prefill_chunk._cache_size() == n_prefill + 1
        assert tel.counters()["engine/jit_cache_miss"] == float(len(spans))
        # the step that built it waited for the whole build, inside it
        (ev,) = spans
        assert ev["dur_ns"] > 0
        assert any(
            s["t0_ns"] <= ev["t0_ns"]
            and ev["t0_ns"] + ev["dur_ns"] <= s["t0_ns"] + s["dur_ns"]
            for s in tel.events() if s["name"] == "engine/prefill"
        )


class TestDeviceNames:
    def test_decode_step_carries_named_scopes(self):
        """The lowered decode step names the expert path, attention and
        the logits projection (metadata for the profiler's op view)."""
        import re

        import jax

        eng = _moe_engine(expert_exec="dual_path_cost", policy="dual_cost")
        lm, B = eng.lm, eng.cfg.n_slots
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, 1), np.int32),
            "position": jax.ShapeDtypeStruct((B,), np.int32),
            "sieve": eng._sieve_state,
        }
        text = jax.jit(lm.decode_step).lower(
            eng.params, batch, eng.cache
        ).as_text(debug_info=True)
        locs = set(re.findall(r'loc\("([^"]*)"', text))
        for scope in ("moe/router", "moe/dispatch", "moe/head", "moe/tail",
                      "moe/combine", "attention", "lm_head"):
            assert any(re.search(rf"(^|/){scope}/", loc) for loc in locs), scope


# ---------------------------------------------------------------------------
# Cluster integration
# ---------------------------------------------------------------------------


class TestClusterTelemetry:
    def _run(self, tel):
        from repro.cluster import (
            ClusterSimulator,
            LengthModel,
            PoissonProcess,
        )
        from repro.cluster.replica import ReplicaConfig
        from repro.sim import SIM_MODELS

        cs = ClusterSimulator(
            SIM_MODELS["qwen3-30b"], b200_pim_system(), policy="sieve",
            n_replicas=2, router_policy="jsq",
            replica_cfg=ReplicaConfig(n_slots=4, prefill_chunk=256),
            seed=0, telemetry=tel,
        )
        arr = PoissonProcess(
            rate=40.0,
            lengths=LengthModel(kind="fixed", prompt_mean=256, output_mean=8),
            seed=2,
        )
        return cs.run(arr, horizon=0.4)

    def test_replica_tracks_and_slo_series(self, tmp_path):
        tel = Telemetry(enabled=True)
        res = self._run(tel)
        assert set(tel.tracks) >= {"replica-0", "replica-1"}
        by_name = {}
        for e in tel.events():
            by_name.setdefault(e["name"], []).append(e)
        assert by_name.get("replica/step") or by_name.get("replica/step_jump")
        # per-request SLO series: one e2e point per retirement, stamped at
        # the retirement's simulated time with the metrics-module value
        assert len(by_name["slo/e2e"]) == len(res.completed)
        from repro.cluster.metrics import request_e2e

        e2es = sorted(e["value"] for e in by_name["slo/e2e"])
        want = sorted(request_e2e(r) for r in res.completed)
        assert e2es == pytest.approx(want)
        # ttft fires at first-token time, so in-flight requests count too
        assert len(by_name["slo/ttft"]) >= len(res.completed)
        assert all(e["value"] >= 0.0 for e in by_name["slo/ttft"])
        # load series exist with sane ranges
        occ = [e["value"] for e in by_name["replica/batch_occupancy"]]
        assert occ and all(0.0 <= v <= 1.0 for v in occ)
        # whole run exports as one multi-process Perfetto timeline
        path = write_trace(tel, str(tmp_path / "cluster.json"))
        doc = json.load(open(path))
        pids = {
            e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"
        }
        assert len(pids) == 2

    def test_cluster_results_identical_with_and_without_telemetry(self):
        res_off = self._run(None)
        res_on = self._run(Telemetry(enabled=True))
        key = lambda res: sorted(
            (r.spec.req_id, r.first_token_time, r.finish_time)
            for r in res.completed
        )
        assert key(res_off) == key(res_on)
