# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV. E1/E2 = Fig. 3 (latency vs H and X), E3 = Table 1 (resources),
# E4 = rowwise-vs-cascade aggregation study (+ the deep-stack depth sweep,
# artifact: BENCH_gru_depth.json).
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import fig3_latency, rowwise_vs_cascade, table1_resources
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    fig3_latency.run(csv=True, iters=120)
    table1_resources.run(csv=True)
    rowwise_vs_cascade.run(csv=True)
    rowwise_vs_cascade.run_depth_sweep(csv=True)


if __name__ == "__main__":
    main()
