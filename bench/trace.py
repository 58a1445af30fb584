"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace (``jax.profiler``, an ``.xplane.pb``) holds device planes
(``/device:TPU:<n>``) whose ``XLA Ops`` line carries one event per device
operation, and host planes carrying the benchmark's own spans
(``bench.<name>`` annotations).  Everything here works on plain
``(name, start_ns, end_ns)`` tuples, so tests can feed synthetic traces.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
NAME_CHARS = 160  # an op's name in the breakdown: its HLO text, cut here


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str) -> dict:
    """-> {"devices": {plane: [(op, start, end)]}, "spans": [(name, start, end)]}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices[plane.name] = [
                (ev.name, int(ev.start_ns), int(ev.end_ns)) for ev in lines["XLA Ops"].events
            ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):], int(ev.start_ns), int(ev.end_ns)))
    return dict(devices=devices, spans=sorted(spans, key=lambda s: s[1]))


def union(intervals) -> list:
    """Merge [start, end) intervals -> sorted disjoint list."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def window_of(spans) -> tuple[int, int]:
    """The traced window: first span start to the end of the last run."""
    runs = [s for s in spans if s[0] == "run"]
    if not runs:
        raise ValueError("trace holds no bench.run span")
    return min(s[1] for s in spans), max(s[2] for s in runs)


class Reduced:
    """Device busy time, gaps and op totals of one traced window."""

    def __init__(self, trace: dict):
        self.spans = trace["spans"]
        self.w0, self.w1 = window_of(self.spans)
        self.busy = {
            dev: union(clip([(s, e) for _, s, e in ops], self.w0, self.w1))
            for dev, ops in trace["devices"].items()
        }
        self.ops = trace["devices"]

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds some operation ran, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(length(b) for b in self.busy.values()) / len(self.busy) / 1e9

    def busy_within(self, name: str) -> float:
        """Device-busy seconds inside the host spans called ``name``
        (averaged over devices)."""
        spans = union([(s, e) for n, s, e in self.spans if n == name])
        if not self.busy:
            return 0.0
        tot = 0
        for b in self.busy.values():
            for s, e in spans:
                tot += length(clip(b, s, e))
        return tot / len(self.busy) / 1e9

    def op_seconds(self, match) -> float:
        """Summed device durations of the ops whose name satisfies
        ``match`` (averaged over devices)."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            tot += sum(min(e, self.w1) - max(s, self.w0) for n, s, e in ops
                       if match(n) and e > self.w0 and s < self.w1)
        return tot / len(self.ops) / 1e9

    def top_ops(self, k: int = 10) -> list:
        totals: dict = defaultdict(int)
        for ops in self.ops.values():
            for n, s, e in ops:
                if e > self.w0 and s < self.w1:
                    totals[n] += min(e, self.w1) - max(s, self.w0)
        n_dev = max(len(self.ops), 1)
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[n[:NAME_CHARS], t / n_dev / 1e9] for n, t in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The k longest device-idle gaps of the first device, each named
        for the host span that covers its midpoint."""
        if not self.busy:
            return []
        busy = next(iter(self.busy.values()))
        edges = [self.w0] + [x for iv in busy for x in iv] + [self.w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            cover = [n for n, a, b in self.spans if a <= mid < b]
            out.append([cover[-1] if cover else "host", (e - s) / 1e9])
        return out
