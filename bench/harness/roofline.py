"""A kernel's share of its roofline, from served work and the trace."""
from __future__ import annotations


def bound(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound) for the work on this chip."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share(run, pattern: str, flops: float, nbytes: float):
    """Percent of the roofline, or None where the trace holds no event of
    the kernel (never 0 for a kernel that was not seen)."""
    t = run.trace.kernel_s(pattern)
    if t <= 0.0 or flops + nbytes <= 0:
        return None
    return 100.0 * bound(flops, nbytes, run.peaks)[0] / t
