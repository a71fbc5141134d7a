"""Device idle time under the program's own host spans.

The serving engine marks the phases of each wave step with host spans in
the profiler's trace (``engine.step`` and its children ``engine.admit``,
``engine.decode`` and so on), on the thread that also holds the window's
span. These spans share the device trace's clock, so the idle intervals
of the device can be intersected with them exactly.
"""
from __future__ import annotations

from typing import Iterable, Optional

from harness.trace import Trace, union

# the engine's span around one whole wave step: a trace without one comes
# from a program that marks no phases, and reads nothing
STEP = "engine.step"


def idle_under(trace: Optional[Trace], names: Iterable[str]
               ) -> Optional[float]:
    """Seconds of the window in which no operation ran on the device while
    the window's thread was inside a span named in ``names``: the idle
    intervals (window minus ``Trace.busy()``) intersected with the union of
    those spans' intervals, clipped to the window. ``None`` for a trace
    with no device plane, or whose window holds no ``engine.step`` span."""
    if trace is None or trace.devices == 0:
        return None
    names = set(names)
    if not any(n == STEP and e > trace.t0 and s < trace.t1
               for n, s, e in trace.host):
        return None
    spans = union([(max(s, trace.t0), min(e, trace.t1))
                   for n, s, e in trace.host
                   if n in names and e > trace.t0 and s < trace.t1])
    busy = trace.busy()
    idle, k = 0, 0
    for s, e in spans:
        covered = 0
        while k < len(busy) and busy[k][1] <= s:
            k += 1
        j = k
        while j < len(busy) and busy[j][0] < e:
            covered += min(e, busy[j][1]) - max(s, busy[j][0])
            j += 1
        idle += (e - s) - covered
    return idle * 1e-9
