"""Share of the traced window (%) in which the device was idle while the
engine was inside the decode call: the copy of the next features, the
dispatch and the wait for the result (the program's ``engine.decode``
span)."""
from harness.spans import idle_under

SPANS = ("engine.decode",)


def read(run):
    s = idle_under(run.trace, SPANS)
    return None if s is None else 100.0 * s / run.trace.window_s
