"""The trace reducer on a small synthetic trace."""
from types import SimpleNamespace as NS

import pytest

from harness import trace


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _planes():
    host = _line("python", [
        (trace.WINDOW, 1000, 9000),            # window: 1000 .. 10000
        ("bench.step", 1000, 4000),            # 1000 .. 5000
        ("dispatch", 1500, 500),               # 1500 .. 2000
        ("bench.client", 5000, 5000),          # 5000 .. 10000
    ])
    dev = _line("XLA Ops", [
        ("fusion.1", 500, 1000),               # clipped to 1000 .. 1500
        ("_decode_kernel", 2000, 1000),        # 2000 .. 3000
        ("copy.2", 2500, 1000),                # overlaps: 2500 .. 3500
        ("_decode_kernel", 6000, 500),         # 6000 .. 6500
        ("late", 11000, 100),                  # outside the window
    ])
    return [NS(name="/host:CPU", lines=[host]),
            NS(name="/device:TPU:0", lines=[dev, _line("XLA Modules", [
                ("jit_step", 0, 20000)])])]


def test_union_busy_and_idle_share():
    t = trace.from_planes(_planes())
    assert t.window_s == pytest.approx(9000e-9)
    assert t.busy() == [(1000, 1500), (2000, 3500), (6000, 6500)]
    assert t.busy_s() == pytest.approx(2500e-9)      # modules line ignored
    assert 1 - t.busy_s() / t.window_s == pytest.approx(6500 / 9000)


def test_kernel_time_by_pattern():
    t = trace.from_planes(_planes())
    assert t.kernel_s(r"_decode_kernel") == pytest.approx(1500e-9)
    assert t.kernel_s(r"fusion") == pytest.approx(500e-9)   # clipped
    assert t.kernel_s(r"nothing") == 0.0
    assert t.top_ops()[0] == ["_decode_kernel", pytest.approx(1500e-9)]


def test_idle_gaps_charged_to_innermost_host_span():
    t = trace.from_planes(_planes())
    gaps = dict((k, v) for k, v in t.idle_gaps())
    # gaps: 1500..2000 (mid 1750: dispatch), 3500..6000 (mid 4750:
    # bench.step), 6500..10000 (mid 8250: bench.client)
    assert gaps == {"dispatch": pytest.approx(500e-9),
                    "bench.step": pytest.approx(2500e-9),
                    "bench.client": pytest.approx(3500e-9)}


def test_union_merges_touching_and_nested():
    assert trace.union([(5, 6), (0, 2), (2, 3), (1, 4)]) == [(0, 4), (5, 6)]


def test_trace_without_window_is_refused():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="window"):
        trace.from_planes(planes)


SEQ = ("%gru_sequence_kernel.1 = f32[32,8,20]{2,1,0:T(8,128)S(1)} "
       "custom-call(f32[8,20]{1,0:T(8,128)S(1)} %broadcast_in_dim.6)")


@pytest.mark.parametrize("metric", ["seq_kernel_roofline.latency",
                                    "seq_kernel_roofline.bulk"])
@pytest.mark.parametrize("name,hit", [
    (SEQ, True),
    ("%gru_stack_sequence_kernel.1 = (f32[32,256,32]{2,1,0:T(8,128)}, "
     "f32[3,256,32]{2,1,0:T(8,128)}) custom-call(%p)", True),
    ("%fusion = f32[8,32,60]{2,1,0:T(8,128)S(1)} fusion(%gru_sequence_kernel"
     ".1)", False),
    ("%gru_stack_decode_kernel.1 = f32[1,8,20]{2,1,0:T(8,128)S(1)} "
     "custom-call(f32[1,8,20]{2,1,0:T(8,128)S(1)} %copy.7)", False),
])
def test_kernel_patterns_match_the_names_a_chip_trace_gives(metric, name,
                                                            hit):
    """Event names as a TPU v5e trace's XLA Ops line gives them."""
    import re
    from harness import spec
    pattern = spec.metric_reader(metric).PATTERN
    assert bool(re.search(pattern, name)) is hit
