"""Share of the traced window (%) in which the device was idle while the
engine was in its per-slot loops: the next-feature staging and the
retiring of answered lanes (the program's ``engine.feed`` and
``engine.retire`` spans)."""
from harness.spans import idle_under

SPANS = ("engine.feed", "engine.retire")


def read(run):
    s = idle_under(run.trace, SPANS)
    return None if s is None else 100.0 * s / run.trace.window_s
