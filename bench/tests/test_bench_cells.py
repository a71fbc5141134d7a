"""Every cell run in-process on the CPU at a tiny window (interpret-mode
kernels): the same code as a run on the chip, printing a last line with
exactly the result line's keys. The mixes are cut down (fewer distinct
windows, fewer bulk slots) so that a tiny window holds them."""
import json

import pytest
from benchcells import CELLS, small

from harness import check, spec


def _keys(res):
    return list(json.loads(json.dumps(res)))


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_prints_the_result_line(name, run_tiny):
    res = run_tiny(name, **small(name))
    assert _keys(res) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = spec.load_cell(name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = res["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["checks"]) == set(cell.limits) <= set(check.NUMBERS)
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run_reports_layers_and_trace_keys(run_tiny):
    res = run_tiny("gru-jet.bulk", trace=True, **small("gru-jet.bulk"))
    assert _keys(res) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    # no device operations on the CPU: no kernel time, so no roofline share
    assert set(res["metrics"]) == {"prefill_share.bulk", "idle_share.bulk",
                                   "mfu.bulk"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_same_inputs_and_classes(run_tiny):
    a, ga = run_tiny(CELLS[-1], keep=True, seed=11, **small(CELLS[-1]))
    b, gb = run_tiny(CELLS[-1], keep=True, seed=11, **small(CELLS[-1]))
    assert (ga["pool"].feats == gb["pool"].feats).all()
    a_out, b_out = list(ga["window"].outs()), list(gb["window"].outs())
    n = min(len(a_out), len(b_out))
    assert n > 0 and a_out[:n] == b_out[:n]


def test_same_sizes_for_every_seed():
    """Every seed offers the same set of request sizes in another order,
    with other feature values; one seed always the same ones."""
    import numpy as np

    from harness import traffic
    tr = {"prompt_len": 3, "served_steps": [5, 9], "distinct": 5}
    a, b = traffic.pool(tr, 2, 1), traffic.pool(tr, 2, 2**40 + 3)
    assert sorted(a.served) == sorted(b.served) == [5, 6, 7, 8, 9]
    assert not np.array_equal(a.feats, b.feats)
    assert np.array_equal(traffic.pool(tr, 2, 7).feats,
                          traffic.pool(tr, 2, 7).feats)
