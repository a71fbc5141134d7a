"""Share of the traced window (%) in which the device was idle while the
engine was admitting queued requests: the queue pop, slots, the bucket
choice and the prompt staging (the program's ``engine.admit`` span)."""
from harness.spans import idle_under

SPANS = ("engine.admit",)


def read(run):
    s = idle_under(run.trace, SPANS)
    return None if s is None else 100.0 * s / run.trace.window_s
