"""Share of the traced window (%) in which the device was idle while the
engine was reading out the classes: the eager argmax and the class
download (the program's ``engine.readout`` span)."""
from harness.spans import idle_under

SPANS = ("engine.readout",)


def read(run):
    s = idle_under(run.trace, SPANS)
    return None if s is None else 100.0 * s / run.trace.window_s
