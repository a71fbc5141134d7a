"""Set-up: process start to the first timed step (init, weights, warm-up
of the cell's own shapes from the compile cache)."""


def read(run):
    return run.setup_s
