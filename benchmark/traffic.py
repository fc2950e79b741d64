"""One general generator for every traffic mix, read from its data file.

Every seed gets the same multiset of sizes, drawn at evenly spaced
quantiles of the mix's laws, in an order and with prompt tokens of its
own.  So seeds change which request comes when and what it says, not how
much work the window holds.

Closed loop (the one loop there is): one client per slot; a client sends
its next request when its last one completes.  The first request of each
client is cut to a uniform share of its output length, which starts the
slots at staggered points of their requests as in a loop that has been
running.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np
from statistics import NormalDist


@dataclass
class Spec:
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(law: Dict, n: int) -> np.ndarray:
    if law["law"] != "lognormal":
        raise ValueError(f"unknown output-length law {law['law']!r}")
    z = np.array([NormalDist().inv_cdf(float(u)) for u in _quantiles(n)])
    x = law["median"] * np.exp(law["sigma"] * z)
    return np.clip(np.round(x), law["min"], law["max"]).astype(np.int64)


def prompt_lengths(buckets: Dict[str, float], n: int) -> np.ndarray:
    sizes = sorted(int(k) for k in buckets)
    w = np.array([buckets[str(s)] for s in sizes], float)
    counts = np.floor(w / w.sum() * n).astype(int)
    # hand out the remainder by largest fractional part
    rest = np.argsort(-(w / w.sum() * n - counts))[: n - counts.sum()]
    counts[rest] += 1
    return np.repeat(sizes, counts)


def bucket_sizes(mix: Dict) -> List[int]:
    return sorted(int(k) for k in mix["prompt_buckets"])


class Population:
    """Endless stream of (prompt length, output length) pairs: the fixed
    population in a fresh order per pass, and prompt tokens per request."""

    def __init__(self, mix: Dict, vocab: int, rng: np.random.Generator):
        n = int(mix["population"])
        self.prompts = prompt_lengths(mix["prompt_buckets"], n)
        self.outputs = output_lengths(mix["output"], n)
        self.vocab = vocab
        self.rng = rng
        self._order: List = []

    def next(self) -> Spec:
        if not self._order:
            self._order = list(zip(self.rng.permutation(self.prompts),
                                   self.rng.permutation(self.outputs)))
        p, o = self._order.pop()
        toks = self.rng.integers(0, self.vocab, size=int(p)).astype(np.int32)
        return Spec(prompt=toks, max_new=int(o))


def first_requests(pop: Population, n_clients: int, rng: np.random.Generator) -> List[Spec]:
    """The closed loop's first request per client, cut to a uniform share
    (evenly spaced, in random order) of its output length."""
    shares = rng.permutation(_quantiles(n_clients))
    out = []
    for s in shares:
        spec = pop.next()
        spec.max_new = max(1, int(round(s * spec.max_new)))
        out.append(spec)
    return out

