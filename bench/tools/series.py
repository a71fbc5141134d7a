"""One run of a cell, as ``bench/run.py`` makes it, that also keeps the
window's event times, for a look at where a tail's spread comes from.

    python bench/tools/series.py --workload gru-jet.latency --seed 7 \\
        --seconds 20 --out chiprun_out/series/latency-7-a.npz

Writes ``sent`` and ``done`` (seconds from the window's start, one entry
per answered event) and prints the run's result line. The run itself is
``bench/run.py``'s: the same set-up, window and check.
"""
import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from harness import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU (no device numbers)")
    args = ap.parse_args(argv)
    import run as bench_run
    bench_run.enable_compile_cache()
    import jax
    from harness.cell import run_cell
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("series: no TPU", file=sys.stderr)
        return 3
    peaks = None if jax.devices()[0].platform == "tpu" else {
        "flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    cell = spec.load_cell(args.workload)

    def hook(window, result, **_):
        t0 = window.t0
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        np.savez_compressed(
            args.out, sent=np.asarray(window.sent) - t0,
            done=np.asarray(window.done) - t0, seconds=window.seconds)

    res = run_cell(cell, args.seed, args.seconds, False, t_start=T_START,
                   peaks=peaks, hook=hook)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
