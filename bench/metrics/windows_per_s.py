"""Windows classified in the window over the window's seconds."""


def read(run):
    w = run.window
    return sum(d <= w.t1 for d in w.done) / run.seconds
