"""Readings that set a cell's ``correct`` limits: the program's numbers on
many seeds, and the control's, the plain reference computed in the next
precision below the configuration's (three bfloat16 passes for float32 at
``highest``) in the program's place, read at every position and state the
run compared. With ``--fault`` the program runs with that fault planted
(``tools/faults.py``) and the readings are the broken run's.

    python bench/tools/readings.py --workload gru-jet.bulk --seconds 20 \\
        --seeds 101 102 103 [--fault scatter_one]

Runs every seed in one process on the chip (the cell's own traffic and
window) and prints one JSON line per seed. The benchmark's own runs never
compute the control or plant a fault.
"""
import argparse
import contextlib
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import check, spec  # noqa: E402


def control_hook(out: dict):
    def hook(ref, params, pool, window, rows, pairs, result):
        items = check.compared(window)
        used = {j for j, _ in items}
        hi = check.reference_logits(ref, params, pool, used, "highest")
        lo = check.reference_logits(ref, params, pool, used, "high")
        out["control_gap"] = check.control_gap(items, hi, lo)
        out.update(check.state_stats(ref, params, pool, rows, pairs))
        ctl = check.state_stats(ref, params, pool, rows, pairs, "high")
        out.update({"control_" + k: v for k, v in ctl.items()})
        out["positions"] = sum(len(o) for _, o in items)
        out["states"] = len(pairs)
    return hook


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU (no device numbers)")
    args = ap.parse_args(argv)
    import run as bench_run
    bench_run.enable_compile_cache()
    import jax
    from harness.cell import run_cell
    from tools import faults
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("readings: no TPU", file=sys.stderr)
        return 3
    peaks = None if jax.devices()[0].platform == "tpu" else {
        "flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    plant = (faults.planted(args.fault) if args.fault
             else contextlib.nullcontext())
    with plant:
        for seed in args.seeds:
            out = {"workload": args.workload, "seed": seed,
                   "fault": args.fault}
            res = run_cell(cell, seed, args.seconds, False, t_start=T_START,
                           peaks=peaks, hook=control_hook(out))
            out.update(correct=res["correct"],
                       attempted=res["attempted"],
                       **{k: c["value"] for k, c in res["checks"].items()},
                       metrics={k: v["value"]
                                for k, v in res["metrics"].items()})
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
