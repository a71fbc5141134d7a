"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The run records the trace with JAX's profiler (``record``) and marks its
measured window with a host span named ``WINDOW``. Device operations are
the events of the ``XLA Ops`` line of each ``/device:TPU:n`` plane; busy
time is the union of their intervals inside the window. Each idle gap
between them is charged to the innermost host span of the benchmark's
thread that covers the gap's midpoint: what the host was doing while the
device waited.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import List, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"

Span = Tuple[str, int, int]     # (name, start ns, end ns)


@dataclass
class Trace:
    t0: int                              # window bounds, trace clock (ns)
    t1: int
    devices: int                         # device planes seen
    ops: List[Span] = field(default_factory=list)
    host: List[Span] = field(default_factory=list)   # the window's thread

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, spans) -> List[Tuple[int, int]]:
        return [(max(s, self.t0), min(e, self.t1)) for _, s, e in spans
                if e > self.t0 and s < self.t1]

    def busy(self) -> List[Tuple[int, int]]:
        """Union of the device operations' intervals in the window."""
        return union(self._clip(self.ops))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(e - s for s, e in self.busy()) * 1e-9 / max(self.devices, 1)

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches ``pattern``
        (a regular expression searched in the event name)."""
        rx = re.compile(pattern)
        return sum(e - s for s, e in
                   self._clip([o for o in self.ops if rx.search(o[0])])) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        tot = defaultdict(int)
        for name, s, e in self.ops:
            if e > self.t0 and s < self.t1:
                tot[name] += min(e, self.t1) - max(s, self.t0)
        return [[k, v * 1e-9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle seconds in the window, summed by the innermost host span
        over each gap's midpoint; the ``n`` largest."""
        edges = [self.t0]
        for s, e in self.busy():
            edges += [s, e]
        edges.append(self.t1)
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = innermost(self.host, [(a + b) // 2 for a, b in gaps])
        tot = defaultdict(int)
        for (a, b), label in zip(gaps, labels):
            tot[label] += b - a
        return [[k, v * 1e-9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def union(iv) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def innermost(host: List[Span], times: List[int]) -> List[str]:
    """For each of the ascending ``times``, the name of the innermost span
    of one thread's properly nested ``host`` spans that covers it."""
    spans = sorted(host, key=lambda h: (h[1], -h[2]))
    stack: List[Span] = []
    out, k = [], 0
    for t in times:
        while k < len(spans) and spans[k][1] <= t:
            while stack and stack[-1][2] < spans[k][1]:
                stack.pop()
            stack.append(spans[k])
            k += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else "(no host span)")
    return out


def _events(line) -> List[Span]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def from_planes(planes) -> Trace:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events`` carrying ``name``, ``start_ns`` and
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    ops, devices, window, host = [], 0, None, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
            continue
        for line in plane.lines:
            evs = _events(line)
            marks = [e for e in evs if e[0] == WINDOW]
            if marks:
                window, host = marks[0], evs
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return Trace(t0=window[1], t1=window[2], devices=devices, ops=ops,
                 host=[h for h in host if h[0] != WINDOW])


def load(log_dir: str) -> Trace:
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {files}")
    return from_planes(jax.profiler.ProfileData.from_file(files[0]).planes)
