"""Share of the traced window (%) in which the device was idle while the
engine was writing admitted rows into the wave cache: the slot index, the
scatter's dispatch with every state array of the cache and of the fresh
prefill, and the slots filled (the program's ``engine.scatter`` span)."""
from harness.spans import idle_under

SPANS = ("engine.scatter",)


def read(run):
    s = idle_under(run.trace, SPANS)
    return None if s is None else 100.0 * s / run.trace.window_s
