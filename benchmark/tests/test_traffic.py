"""Every seed gets the same multiset of sizes, in its own order."""

import numpy as np

from benchmark import traffic as tr
from benchmark.run import load_json
from benchmark.tests import tiny

MIXES = ["decode-bimodal"]


def _mix(name):
    return load_json(tiny.REPO / "benchmark" / "traffic" / f"{name}.json")


def test_population_is_seed_independent():
    for name in MIXES:
        mix = _mix(name)
        pops = []
        for seed in (1, 2**33 + 1):
            pop = tr.Population(mix, 1000, np.random.default_rng(seed))
            specs = [pop.next() for _ in range(mix["population"])]
            pops.append(sorted((len(s.prompt), s.max_new) for s in specs))
            assert {len(s.prompt) for s in specs} == set(tr.bucket_sizes(mix))
            assert all(mix["output"]["min"] <= s.max_new <= mix["output"]["max"] for s in specs)
        assert sorted(p for p, _ in pops[0]) == sorted(p for p, _ in pops[1])
        assert sorted(o for _, o in pops[0]) == sorted(o for _, o in pops[1])


def test_bucket_weights_and_median():
    mix = _mix("decode-bimodal")
    n = mix["population"]
    lens = tr.prompt_lengths(mix["prompt_buckets"], n)
    for size, w in mix["prompt_buckets"].items():
        assert abs((lens == int(size)).mean() - w) <= 1.0 / n
    outs = tr.output_lengths(mix["output"], n)
    assert abs(np.median(outs) - mix["output"]["median"]) <= 2


def test_closed_loop_first_requests_are_staggered():
    mix = _mix("decode-bimodal")
    pop = tr.Population(mix, 1000, np.random.default_rng(3))
    first = tr.first_requests(pop, 48, np.random.default_rng(4))
    assert len(first) == 48
    assert len({s.max_new for s in first}) > 24
