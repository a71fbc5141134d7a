"""Model FLOPs served in the window (every layer's products and the head,
live rows and real steps only, prefill and decode) over the window and the
chip's peak (%)."""


def read(run):
    W, s = run.work, run.sizes
    flops = (run.rows["decode"] * W.model_flops_per_step(s)
             + W.prefill_model_flops(s, run.rows["prefill"],
                                     run.rows["prefill_steps"]))
    return 100.0 * flops / run.seconds / run.peaks["flops_per_s"]
