"""Share of the window (%) inside the engine's prefill calls (its
``prefill_times``: host clock around each call, to ``block_until_ready``)."""


def read(run):
    return 100.0 * run.prefill_s.sum() / run.seconds
