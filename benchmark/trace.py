"""Reduction from a profiler trace to the device numbers the metrics use.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are ``/device:TPU:<n>``;
on each, the ``XLA Ops`` line holds one event per operation run and the
``XLA Modules`` line one event per program run.  Host events named
``bench/...`` are the benchmark's own ``TraceAnnotation``s.

* busy: the union of a device's operation intervals;
* per-operation device time: the summed durations by operation name;
* operations by kind (name less instance number) or by result shape;
* programs: each module run with its interval, and the operations
  inside it;
* idle gaps: the spaces between busy intervals, each named by the host
  annotation that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "bench/"


def op_name(hlo: str) -> str:
    """An operation's own name from the trace's HLO text:
    ``"%swiglu_gemv.7 = bf16[...] custom-call(...)"`` -> ``"swiglu_gemv.7"``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(hlo: str) -> str:
    """The name without its instance number: ``"swiglu_gemv"``."""
    name = op_name(hlo)
    head, _, tail = name.rpartition(".")
    return head if tail.isdigit() else name


_SHAPE = re.compile(r"\(?[a-z0-9]+\[([0-9,]*)\]")


def op_shape(hlo: str) -> Optional[Tuple[int, ...]]:
    """The dims of an operation's (first) result from the trace's HLO text:
    ``"%f.3 = bf16[128,2048,768]{2,1,0} fusion(...)"`` -> ``(128, 2048, 768)``."""
    if " = " not in hlo:
        return None
    m = _SHAPE.match(hlo.split(" = ", 1)[1])
    if m is None:
        return None
    return tuple(int(x) for x in m.group(1).split(",") if x)


CONTAINERS = ("while", "conditional", "call")


@dataclass
class Events:
    names: List[str]
    start: np.ndarray  # ns
    end: np.ndarray  # ns

    @classmethod
    def of(cls, evs) -> "Events":
        names = [e[0] for e in evs]
        start = np.array([e[1] for e in evs], np.float64)
        end = np.array([e[1] + e[2] for e in evs], np.float64)
        o = np.argsort(start, kind="stable")
        return cls([names[i] for i in o], start[o], end[o])

    def __len__(self) -> int:
        return len(self.names)

    def select(self, mask) -> "Events":
        idx = np.flatnonzero(mask)
        return Events([self.names[i] for i in idx], self.start[idx], self.end[idx])

    def matching(self, substrings) -> "Events":
        """Events whose name contains one of ``substrings`` (programs)."""
        return self.select([any(s in n for s in substrings) for n in self.names])

    def of_kind(self, kinds) -> "Events":
        """Operations whose own name, less its instance number, is one of
        ``kinds``: operands named in the HLO text do not count."""
        return self.select([op_kind(n) in kinds for n in self.names])

    def of_shape(self, shapes) -> "Events":
        """Operations whose result has one of ``shapes``."""
        shapes = {tuple(x) for x in shapes}
        return self.select([op_shape(n) in shapes for n in self.names])

    def leaves(self) -> "Events":
        """Operations other than control-flow containers, whose time their
        own operations already hold."""
        return self.select([op_kind(n) not in CONTAINERS for n in self.names])

    def total(self) -> float:
        return float((self.end - self.start).sum())


@dataclass
class Trace:
    ops: Dict[int, Events]  # device id -> operations
    modules: Dict[int, Events]  # device id -> program runs
    host: Events  # the benchmark's annotations


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if line.name == OPS_LINE:
                    ops[dev] = Events.of(evs)
                elif line.name == MODULES_LINE:
                    modules[dev] = Events.of(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return Trace(ops, modules, Events.of(host))


def union(ev: Events) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in zip(ev.start, ev.end):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def busy_ns(ev: Events) -> float:
    return float(sum(b - a for a, b in union(ev)))


def by_name(ev: Events) -> Dict[str, float]:
    """Summed durations by operation name (the HLO text cut to the name)."""
    out: Dict[str, float] = {}
    for n, s, e in zip(ev.names, ev.start, ev.end):
        k = op_name(n)
        out[k] = out.get(k, 0.0) + (e - s)
    return out


def inside(ev: Events, outer: Events) -> Events:
    """The events of ``ev`` that lie within some interval of ``outer``."""
    if not len(ev) or not len(outer):
        return ev.select(np.zeros(len(ev), bool))
    i = np.searchsorted(outer.start, ev.start, side="right") - 1
    ok = (i >= 0) & (ev.end <= outer.end[np.maximum(i, 0)] + 1.0)
    return ev.select(ok)


def idle_gaps(ev: Events, host: Events, top: int = 10) -> List[Tuple[str, float]]:
    """Longest gaps between busy intervals, each named by the host
    annotation overlapping it most ("none" where no annotation does)."""
    iv = union(ev)
    gaps = [(iv[k][1], iv[k + 1][0]) for k in range(len(iv) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        best, best_ov = "none", 0.0
        for n, s, e in zip(host.names, host.start, host.end):
            ov = min(b, e) - max(a, s)
            if ov > best_ov:
                best, best_ov = n, ov
        out.append((best, (b - a) * 1e-9))
    return out


def top_ops(ev: Events, top: int = 10) -> List[Tuple[str, float]]:
    """The operations (containers left out) that took most device time."""
    return [(n, t * 1e-9) for n, t in
            sorted(by_name(ev.leaves()).items(), key=lambda kv: -kv[1])[:top]]


def first_device(tr: Trace) -> Optional[int]:
    return min(tr.ops) if tr.ops else None
