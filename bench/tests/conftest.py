"""The benchmark's own CPU tests: the harness is importable from bench/,
the program from src/ (the harness adds it when it builds the system)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# stand-in peaks for runs on the CPU, where no device number is measured
CPU_PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def run_tiny():
    """Run a cell in-process on the CPU with its traffic file's parameters
    overridden by ``traffic``; returns the result line's object (and the
    hook's keywords when ``keep=True``)."""
    from harness import spec
    from harness.cell import run_cell

    def go(name, seconds=0.4, trace=False, keep=False, seed=2**33 + 1,
           **traffic):
        cell = spec.load_cell(name)
        cell.traffic.update(traffic)
        got = {}
        res = run_cell(cell, seed, seconds, trace, t_start=0.0,
                       peaks=CPU_PEAKS, hook=lambda **kw: got.update(kw))
        return (res, got) if keep else res
    return go
