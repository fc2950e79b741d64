"""The readers of the decode step's host tail, on synthetic span lists:
means over the window's spans, the count of programs built, and None for
a program without the decode tail's spans."""

from pathlib import Path

import pytest

from benchmark import run as runmod

ROOT = Path(__file__).resolve().parents[2]


def span(name, t0, dur):
    return {"kind": "span", "name": name, "track": "main", "t0_ns": t0,
            "dur_ns": dur, "value": float("nan")}


def ctx_of(spans):
    return runmod.Context(dm=None, peak={}, window_s=1.0, work={}, spans=spans)


def read(name, ctx):
    return runmod.metric_reader(ROOT, name)(ctx)


STEPS = [
    span("engine/step", 0, 100_000_000),
    span("engine/decode", 1_000_000, 80_000_000),
    span("engine/decode_launch", 1_000_000, 2_000_000),
    span("engine/decode_wait", 3_000_000, 70_000_000),
    span("engine/decode_logits", 73_000_000, 6_000_000),
    span("engine/decode_sample", 81_000_000, 7_000_000),
    span("engine/sieve_host", 90_000_000, 2_000_000),
    span("engine/step", 100_000_000, 100_000_000),
    span("engine/decode", 101_000_000, 80_000_000),
    span("engine/decode_launch", 101_000_000, 2_000_000),
    span("engine/decode_logits", 173_000_000, 4_000_000),
    span("engine/decode_sample", 181_000_000, 5_000_000),
    span("engine/sieve_host", 190_000_000, 3_000_000),
]


@pytest.mark.parametrize("metric,value", [
    ("logits_to_host_ms_per_step.decode", 5.0),
    ("sample_ms_per_step.decode", 6.0),
    ("sieve_host_ms_per_step.decode", 2.5),
    ("compiles.decode", 0.0),
])
def test_reads_the_window_spans(metric, value):
    assert read(metric, ctx_of(STEPS)) == pytest.approx(value)


def test_compiles_counts_programs_built():
    spans = STEPS + [span("engine/compile/jit(_prefill_chunk_impl)", 2_000_000, 900_000),
                     span("engine/compile/jit(decode_step)", 120_000_000, 700_000),
                     span("engine/compiled_elsewhere", 0, 1)]
    assert read("compiles.decode", ctx_of(spans)) == 2.0


@pytest.mark.parametrize("metric", [
    "logits_to_host_ms_per_step.decode",
    "sample_ms_per_step.decode",
    "sieve_host_ms_per_step.decode",
    "compiles.decode",
])
def test_none_without_the_decode_tail_spans(metric):
    # a program whose decode step is one span: engine/sieve_host exists
    # there, but covers other work, so it is not read either
    parent = [s for s in STEPS if s["name"] in
              ("engine/step", "engine/decode", "engine/sieve_host")]
    assert read(metric, ctx_of(parent)) is None
    assert read(metric, ctx_of([])) is None
