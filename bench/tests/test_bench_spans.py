"""Device idle time under the engine's spans, on small synthetic traces,
and the metrics that read it."""
import json
from types import SimpleNamespace as NS

import pytest

from harness import spans, spec, trace

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
READS = {"admit_idle_share": ("engine.admit",),
         "slot_idle_share": ("engine.feed", "engine.retire"),
         "decode_idle_share": ("engine.decode",),
         "readout_idle_share": ("engine.readout",)}
NEW = [f"{m}.{c}" for m in READS for c in ("latency", "bulk")]


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _planes(host_events, device=True):
    host = _line("python", [(trace.WINDOW, 1000, 9000)] + host_events)
    dev = _line("XLA Ops", [
        ("fusion.1", 500, 1000),               # clipped: 1000 .. 1500
        ("_decode_kernel", 2000, 1000),        # 2000 .. 3000
        ("copy.2", 2500, 1000),                # 2500 .. 3500
        ("_decode_kernel", 6000, 500),         # 6000 .. 6500
    ])
    planes = [NS(name="/host:CPU", lines=[host])]
    if device:
        planes.append(NS(name="/device:TPU:0", lines=[dev]))
    return planes


# one step 800 .. 10500, its phases below (the window is 1000 .. 10000, the
# device busy in it 1000-1500, 2000-3500 and 6000-6500)
STEP = [("engine.step", 800, 9700),
        ("engine.admit", 800, 1000),           # 800 .. 1800: clipped to 1000
        ("engine.decode", 1800, 2200),         # 1800 .. 4000
        ("PjitFunction(gru_decode)", 1900, 1800),
        ("CommonPjRtBuffer::Await", 3600, 300),   # 3600 .. 3900
        ("engine.readout", 4000, 3000),        # 4000 .. 7000
        ("engine.feed", 7000, 1000),           # 7000 .. 8000
        ("engine.retire", 8000, 2500)]         # 8000 .. 10500: clipped


@pytest.mark.parametrize("names,idle_ns", [
    # 1000..1800 less busy 1000..1500
    (("engine.admit",), 300),
    # 1800..4000 less 2000..3500; the gaps' innermost spans are JAX's own
    (("engine.decode",), 700),
    # 4000..7000 less 6000..6500
    (("engine.readout",), 2500),
    # two spans, the second clipped at the window's end (10000)
    (("engine.feed", "engine.retire"), 3000),
    # the whole step: the window less its busy time
    (("engine.step",), 9000 - 2500),
    # nested names are counted once
    (("engine.decode", "PjitFunction(gru_decode)"), 700),
    (("engine.nothing",), 0),
])
def test_idle_under_spans_is_the_exact_intersection(names, idle_ns):
    t = trace.from_planes(_planes(STEP))
    assert spans.idle_under(t, names) == pytest.approx(idle_ns * 1e-9)


def test_children_add_up_to_the_steps_idle_time():
    t = trace.from_planes(_planes(STEP))
    parts = sum(spans.idle_under(t, names) for names in READS.values())
    assert parts == pytest.approx(spans.idle_under(t, ("engine.step",)))


@pytest.mark.parametrize("host,device", [
    (STEP, False),                                  # a CPU run
    ([h for h in STEP if h[0] != "engine.step"], True),   # no step span
    ([("engine.step", 20000, 500)] + STEP[1:], True),     # none in window
])
def test_nothing_to_read_is_none_not_zero(host, device):
    t = trace.from_planes(_planes(host, device))
    assert spans.idle_under(t, ("engine.decode",)) is None
    run = NS(trace=t)
    for name in NEW:
        assert spec.metric_reader(name).read(run) is None


def test_untraced_run_reads_none():
    assert spans.idle_under(None, ("engine.decode",)) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_give_a_share_of_the_window(name):
    t = trace.from_planes(_planes(STEP))
    reader = spec.metric_reader(name)
    assert reader.SPANS == READS[name.split(".")[0]]
    want = 100.0 * spans.idle_under(t, reader.SPANS) / t.window_s
    assert reader.read(NS(trace=t)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_has_its_entry_and_one_cell(name):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    cell = "gru-jet." + name.split(".")[1]
    moves = {"gru-jet.latency": "event_p99_ms",
             "gru-jet.bulk": "windows_per_s"}[cell]
    assert entry["workloads"] == [cell]
    assert entry["moves"] == moves
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "lower", "program_span")
    assert callable(spec.metric_reader(name).read)
