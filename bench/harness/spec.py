"""The benchmark's data: BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by name under ``root``:

- ``BENCHMARK.json``: configurations, cells (``workloads``) and metrics;
- the configuration's ``file`` (sizes, precision, the backend it runs on);
- ``bench/traffic/<traffic>.json``: the mix's parameters, read by
  :mod:`harness.traffic` and :mod:`harness.drive`;
- ``bench/limits/<cell>.json``: the limits ``correct`` is held to;
- ``bench/metrics/<metric>.py``: a reader with ``read(run)``;
- ``bench/reference/<family>.py`` and ``bench/work/<family>.py``: the plain
  reference and the operation counts of the configuration's family.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class SpecError(ValueError):
    """The benchmark's files do not describe a runnable cell."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, as it is run
    traffic: dict           # the traffic file, with its name under "name"
    limits: dict            # number name -> limit
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    traffic["name"] = w["traffic"]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def family_module(kind: str, family: str, root: Path = ROOT):
    """``kind`` is ``reference`` or ``work``."""
    return load_module(root / "bench" / kind / f"{family}.py",
                       f"bench_{kind}_{family}")


def metric_reader(metric: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = _json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no published peaks for device {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
