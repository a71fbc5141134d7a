"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload gru-jet.latency --seed 7 --seconds 20 \\
        --trace 0

Loads the cell named in BENCHMARK.json, builds the system under test from
the checkout's ``src/``, warms up every shape the cell's traffic uses,
measures for ``--seconds``, checks every answered request against the
plain reference, and prints one JSON object as the last line of standard
output (the end-to-end metrics with ``--trace 0``, the per-layer metrics
from a profiler trace with ``--trace 1``). Runs only on a TPU with as many
chips as the cell asks for: anywhere else it exits non-zero and prints no
result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import spec  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else one fixed directory inside the checkout, so that
    only a cell's first run in a checkout compiles."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        enable_compile_cache()
        import jax
        devices = jax.devices()
    except (spec.SpecError, ImportError, RuntimeError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return 3
    from harness.cell import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
