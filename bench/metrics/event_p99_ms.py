"""99th percentile (ms) over every event answered in the window, from the
moment the client sent it to its class in the client's hands."""
from harness.stats import quantile


def read(run):
    w = run.window
    return quantile([d - u for d, u in zip(w.done, w.sent)], 0.99) * 1e3
