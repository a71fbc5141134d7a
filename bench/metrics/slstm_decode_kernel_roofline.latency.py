"""The fused sLSTM decode kernel's share of its roofline (%): the least
time the chip needs for the decode steps served in the window (live
row-steps, weights once a call; ``bench/work/slstm.py``) over the device
time of the kernel's events in the trace."""
from harness.roofline import share

# the kernel's custom call in the device trace's XLA Ops line, e.g.
# "%slstm_stack_decode_kernel.1 = (f32[1,8,20]{...}, ...) custom-call(...)"
PATTERN = r"^%slstm_stack_decode_kernel(\.\d+)? = "


def read(run):
    flops, nbytes = run.work.decode_kernel(
        run.sizes, run.calls["decode"], run.rows["decode"])
    return share(run, PATTERN, flops, nbytes)
