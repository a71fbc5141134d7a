"""The fused sLSTM sequence (prefill) kernel's share of its roofline (%):
the least time the chip needs for the prefill work served in the window
(live rows and real prompt steps; the larger of its operations over peak
FLOP/s and its bytes over peak HBM bandwidth, ``bench/work/slstm.py``)
over the device time of the kernel's events in the trace."""
from harness.roofline import share

# the kernel's custom call in the device trace's XLA Ops line, e.g.
# "%slstm_stack_sequence_kernel.1 = (f32[32,8,20]{...}, ...) custom-call(...)"
PATTERN = r"^%slstm_stack_sequence_kernel(\.\d+)? = "


def read(run):
    flops, nbytes = run.work.sequence_kernel(
        run.sizes, run.calls["prefill"], run.rows["prefill"],
        run.rows["prefill_steps"])
    return share(run, PATTERN, flops, nbytes)
