"""BENCHMARK.json and the files it names: its required shape, the peaks
table, a cell added by files alone, and the refusal to run off the chip."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import spec

ROOT = spec.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    runs = 2 + 14 * 24
    assert 1200 + runs * (BENCH["run_seconds"] + 60) + 24 * 180 <= 43200


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in BENCH[group]]
        assert len(got) == len(set(got))


def test_every_cell_reports_setup_an_end_to_end_and_a_layer_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in CELLS:
        c = spec.load_cell(cell)
        got = [m["name"] for m in c.end_to_end]
        assert "setup_s" in got and len(got) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert m["moves"] in got


def test_every_metric_has_its_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"front end", "scheduler", "model step", "kernels",
                      "device"}


def test_unknown_device_is_refused():
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="no published peaks"):
        spec.peaks("cpu")


P99 = '"""p99 from sending (ms)."""\nfrom harness.stats import quantile\n' \
    '\n\ndef read(run):\n    w = run.window\n    return quantile(' \
    '[d - u for d, u in zip(w.done, w.sent)], 0.99) * 1e3\n'


@pytest.mark.parametrize("served", [[3, 5], [1, 1]])
def test_a_cell_is_added_by_files_alone(tmp_path, served):
    """A new cell, traffic mix and metric in a copy of the benchmark's
    files, loaded by name and run, with no edit to any harness code:
    requests served several classes each, or one window and one class."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "gru-jet.tiny", "config": "gru-jet",
                               "traffic": "tiny", "chips": 1,
                               "why": "a test cell"})
    bench["end_to_end"].append({"name": "tiny_p99_ms", "unit": "ms",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["gru-jet.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench/metrics/tiny_p99_ms.py").write_text(P99)
    tiny = json.loads((ROOT / "bench/traffic/bulk.json").read_text())
    tiny.update(slots=2, outstanding=3, served_steps=served, distinct=4,
                warm_admits=[1, 2])
    (tmp_path / "bench/traffic/tiny.json").write_text(json.dumps(tiny))
    shutil.copy(ROOT / "bench/limits/gru-jet.bulk.json",
                tmp_path / "bench/limits/gru-jet.tiny.json")
    cell = spec.load_cell("gru-jet.tiny", root=tmp_path)
    assert cell.traffic["served_steps"] == served
    from harness.cell import run_cell
    res = run_cell(cell, 3, 0.5, False, t_start=0.0, root=tmp_path,
                   peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert res["correct"] and res["attempted"] > 0, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "tiny_p99_ms"}


def test_missing_file_is_a_spec_error(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    with pytest.raises(spec.SpecError, match="missing benchmark file"):
        spec.load_cell(CELLS[0], root=tmp_path)


def test_limits_naming_an_unknown_number_are_refused(run_tiny, monkeypatch):
    from harness import spec as harness_spec
    load = harness_spec.load_cell

    def with_bad_limit(name, root=ROOT):
        cell = load(name, root)
        cell.limits["state_mean"] = 1.0
        return cell
    monkeypatch.setattr(harness_spec, "load_cell", with_bad_limit)
    with pytest.raises(spec.SpecError, match="state_mean"):
        run_tiny(CELLS[0], seconds=0.2, distinct=8)


def test_cli_refuses_a_machine_without_the_chip():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload", CELLS[0],
         "--seed", str(2**33 + 7), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(Path.home())})
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "Nothing was run" in proc.stderr
