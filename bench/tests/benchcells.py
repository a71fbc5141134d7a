"""The cells the benchmark's CPU tests run, read from BENCHMARK.json."""
import json
from pathlib import Path

# the cells of BENCHMARK.json, and how each traffic mix is cut down so
# that a CPU run in interpret mode answers its requests in a short window
ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SMALL = {"latency": {"distinct": 64},
         "bulk": {"slots": 16, "outstanding": 32, "warm_admits": [16],
                  "distinct": 64}}


def small(cell: str) -> dict:
    """The cut-down traffic overrides for ``cell``'s mix."""
    return SMALL.get(cell.split(".", 1)[1], {})
