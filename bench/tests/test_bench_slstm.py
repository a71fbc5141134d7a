"""The sLSTM cell's plain reference (``reference/slstm.py``) and work counts
(``work/slstm.py``), and the readers of the ``slstm-jet.latency`` cell.

The reference is held against the served path (bucketed, masked prefill
and then decode steps through the cache, on the CPU in interpret mode)
and against the program's own dense oracle, which is independent code of
the same equations; its lower-precision control departs from it by more
than the cell's limits."""
import dataclasses
import json
import re
from types import SimpleNamespace as NS

import numpy as np
import pytest

from harness import check, spans, spec, system, trace

CELL = "slstm-jet.latency"
REF = spec.family_module("reference", "slstm")
WORK = spec.family_module("work", "slstm")
SIZES = spec.load_cell(CELL).config
LIMITS = spec.load_cell(CELL).limits


def _key(seed):
    import jax
    return jax.random.key(seed)


def _feats(seed, n, t, x=5):
    return np.random.default_rng(seed).normal(size=(n, t, x)).astype(
        np.float32)


def _widest(a, b):
    """Each row's widest |a - b| over its leaves and units: a, b (4L, N, H)."""
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return np.swapaxes(d, 0, 1).reshape(d.shape[1], -1).max(1)


def test_served_path_matches_the_reference_logits_and_every_leaf():
    """Rows of several prompt lengths, left-padded into one bucket and
    prefilled under a mask, then decoded step by step through the cache:
    the logits at every served position and all four leaves (c, n, m, h)
    of the final state against the reference."""
    import jax.numpy as jnp
    from repro.distributed.sharding import ShardCtx
    from repro.models import api as mapi

    cfg = system.program_config(SIZES)
    params = REF.init(SIZES, _key(5))
    api = mapi.get_api(cfg)
    prepared = api.prepare_params(system.program_params(params), cfg,
                                  ShardCtx())
    T, bucket, steps = SIZES["seq_len"], 16, 4
    prompt = np.array([9, 12, 16, 5, 14, 11])
    xs = _feats(1, len(prompt), T)
    feats = np.zeros((len(prompt), bucket, 5), np.float32)
    mask = np.zeros((len(prompt), bucket), bool)
    for i, s in enumerate(prompt):
        feats[i, bucket - s:], mask[i, bucket - s:] = xs[i, :s], True
    logits, cache = api.prefill(prepared, cfg, {"features": jnp.asarray(feats),
                                                "mask": jnp.asarray(mask)},
                                ShardCtx())
    assert len(cache["h"]) == 4
    want = np.asarray(REF.logits(params, jnp.asarray(xs), 0))
    rows = np.arange(len(prompt))
    got = [np.asarray(logits)]
    for k in range(steps):
        lg, cache = api.decode_step(prepared, cfg, cache,
                                    jnp.asarray(xs[rows, prompt + k]),
                                    ShardCtx())
        got.append(np.asarray(lg))
    for k, lg in enumerate(got):
        np.testing.assert_allclose(lg, want[rows, prompt - 1 + k],
                                   rtol=0, atol=1e-5)
    ref_state = REF.states(params, jnp.asarray(xs),
                           jnp.asarray(prompt + steps))
    assert _widest(np.stack(cache["h"]), ref_state).max() <= \
        LIMITS["state_max"]


@pytest.mark.parametrize("layers", [1, 2])
def test_reference_matches_the_programs_dense_oracle(layers):
    """``core/slstm.slstm_stack_reference`` is the program's own
    step-by-step oracle, written apart from the reference: the same
    states, leaf by leaf in the flat (c, n, m, h) order, and logits."""
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.core import slstm as core

    sizes = dict(SIZES, num_layers=layers)
    params = REF.init(sizes, _key(layers))
    xs = jnp.asarray(_feats(2, 5, SIZES["seq_len"]))
    lengths = jnp.asarray([20, 19, 7, 1, 13])
    ref = np.asarray(REF.states(params, xs, lengths))
    assert ref.shape == (4 * layers, 5, SIZES["hidden_dim"])
    mask = jnp.arange(xs.shape[1])[None, :] < lengths[:, None]
    g = dataclasses.replace(get_config("slstm-jet").gru, num_layers=layers)
    oracle, _ = core.slstm_stack_reference(
        params["cells"], core.stack_state0(g, 5), xs, mask=mask)
    np.testing.assert_allclose(ref, np.stack(oracle), rtol=1e-5, atol=1e-5)
    _, hs = core.slstm_stack_reference(params["cells"],
                                       core.stack_state0(g, 5), xs,
                                       return_all=True)
    head = params["head"]
    want = np.asarray(hs) @ np.asarray(head["w"]) + np.asarray(head["b"])
    np.testing.assert_allclose(np.asarray(REF.logits(params, xs, 0)), want,
                               rtol=1e-5, atol=1e-5)


def test_control_departs_by_more_than_the_limits():
    """The reference in three bfloat16 passes against itself in float32,
    on 64 whole windows: the median and the widest state error exceed
    the cell's limits, as the program's stay below them."""
    import jax.numpy as jnp
    params = REF.init(SIZES, _key(11))
    xs = jnp.asarray(_feats(3, 64, SIZES["seq_len"]))
    lengths = jnp.full((64,), SIZES["seq_len"])
    hi = REF.states(params, xs, lengths, "highest")
    e = _widest(REF.states(params, xs, lengths, "high"), hi)
    assert np.median(e) > LIMITS["state_med"]
    assert e.max() > LIMITS["state_max"]
    assert _widest(REF.states(params, xs, lengths, "highest"), hi).max() == 0


def test_the_divisor_floor_never_binds():
    """``n_t >= 1`` from the first step on: the program's max(n, 1e-6) is
    the one departure from the source, and it never changes a state."""
    import jax.numpy as jnp
    params = REF.init(SIZES, _key(7))
    xs = jnp.asarray(_feats(4, 32, SIZES["seq_len"]) * 3)
    for t in (1, 2, 20):
        n = np.asarray(REF.states(params, xs, jnp.full((32,), t)))[1]
        assert n.min() >= 1.0 - 1e-6


def test_work_counted_by_hand_at_h20():
    s = SIZES                                   # X=5, H=20, L=1, C=5
    # (X*4H + H*4H + H*C) multiply-adds: (5*80 + 20*80 + 20*5) * 2
    assert WORK.model_flops_per_step(s) == 4200
    assert WORK.head_flops(s) == 200
    assert WORK.prefill_model_flops(s, rows=2, steps=38) == \
        38 * (4200 - 200) + 2 * 200
    flops, nbytes = WORK.decode_kernel(s, calls=2, rows=8)
    assert flops == 8 * 2 * 20 * 80
    weights = 4 * (20 * 80 + 80)                # U and b, once per call
    assert nbytes == 2 * weights + 8 * 4 * (80 + 2 * 4 * 20)
    flops, nbytes = WORK.sequence_kernel(s, calls=1, rows=8, steps=152)
    assert flops == 152 * 2 * 20 * 80
    assert nbytes == weights + 152 * 4 * (80 + 20) + 8 * 4 * 2 * 4 * 20


# custom-call names as a TPU v5e trace's XLA Ops line gives them (one
# sLSTM layer, 8 slots, the 32-step bucket), cut after the first operand
SEQ = ("%slstm_stack_sequence_kernel.1 = (f32[32,8,20]{2,1,0:T(8,128)}, "
       "f32[1,8,20]{2,1,0:T(8,128)}, f32[1,8,20]{2,1,0:T(8,128)}, "
       "f32[1,8,20]{2,1,0:T(8,128)}, f32[1,8,20]{2,1,0:T(8,128)S(1)}) "
       "custom-call(f32[1,8,20]{2,1,0:T(8,128)S(1)} %broadcast.1, ...)")
DEC = ("%slstm_stack_decode_kernel.1 = (f32[1,8,20]{2,1,0:T(8,128)}, "
       "f32[1,8,20]{2,1,0:T(8,128)}, f32[1,8,20]{2,1,0:T(8,128)}, "
       "f32[1,8,20]{2,1,0:T(8,128)S(1)}) custom-call(f32[1,8,20]"
       "{2,1,0:T(8,128)S(1)} %copy.16, ...)")
GRU_SEQ = ("%gru_sequence_kernel.1 = f32[32,8,20]{2,1,0:T(8,128)S(1)} "
           "custom-call(f32[8,20]{1,0:T(8,128)S(1)} %broadcast_in_dim.6)")
GRU_DEC = ("%gru_stack_decode_kernel.1 = f32[1,8,20]{2,1,0:T(8,128)S(1)} "
           "custom-call(f32[1,8,20]{2,1,0:T(8,128)S(1)} %copy.7)")
FUSION = ("%fusion.3 = f32[8,20]{1,0:T(8,128)} fusion(%slstm_stack_decode"
          "_kernel.1)")


@pytest.mark.parametrize("metric,hits", [
    ("slstm_seq_kernel_roofline.latency", {SEQ}),
    ("slstm_decode_kernel_roofline.latency", {DEC}),
])
def test_kernel_patterns_match_the_names_a_chip_trace_gives(metric, hits):
    pattern = spec.metric_reader(metric).PATTERN
    for name in (SEQ, DEC, GRU_SEQ, GRU_DEC, FUSION):
        assert bool(re.search(pattern, name)) is (name in hits), name


def test_roofline_readers_count_the_cells_work():
    """Each roofline reader divides the least time of the work its count
    gives by the kernel's trace time, and reads nothing where the trace
    holds no event of its kernel."""
    from harness import roofline
    dev = NS(name="XLA Ops", events=[NS(name=SEQ, start_ns=2000,
                                        duration_ns=1000),
                                     NS(name=DEC, start_ns=4000,
                                        duration_ns=500)])
    host = NS(name="python", events=[NS(name=trace.WINDOW, start_ns=1000,
                                        duration_ns=9000)])
    t = trace.from_planes([NS(name="/host:CPU", lines=[host]),
                           NS(name="/device:TPU:0", lines=[dev])])
    peaks = spec.peaks("TPU v5 lite")
    run = NS(trace=t, work=WORK, sizes=SIZES, peaks=peaks,
             calls={"prefill": 1, "decode": 1},
             rows={"prefill": 8, "prefill_steps": 152, "decode": 8})
    seq = spec.metric_reader("slstm_seq_kernel_roofline.latency").read(run)
    want = roofline.bound(*WORK.sequence_kernel(SIZES, 1, 8, 152), peaks)[0]
    assert seq == pytest.approx(100.0 * want / 1000e-9)
    dec = spec.metric_reader("slstm_decode_kernel_roofline.latency").read(run)
    want = roofline.bound(*WORK.decode_kernel(SIZES, 1, 8), peaks)[0]
    assert dec == pytest.approx(100.0 * want / 500e-9)
    dev.events[:] = []
    t = trace.from_planes([NS(name="/host:CPU", lines=[host]),
                           NS(name="/device:TPU:0", lines=[dev])])
    assert spec.metric_reader("slstm_seq_kernel_roofline.latency").read(
        NS(**{**run.__dict__, "trace": t})) is None


@pytest.mark.parametrize("metric,names", [
    ("decode_idle_share.latency", ("engine.decode",)),
    ("slstm_decode_idle_share.latency", ("engine.decode",)),
    ("scatter_idle_share.latency", ("engine.scatter",)),
])
def test_span_readers_read_their_span(metric, names):
    host = NS(name="python", events=[
        NS(name=n, start_ns=s, duration_ns=d) for n, s, d in [
            (trace.WINDOW, 1000, 9000), ("engine.step", 1000, 9000),
            ("engine.scatter", 1500, 1000), ("engine.decode", 3000, 2000)]])
    dev = NS(name="XLA Ops", events=[NS(name=DEC, start_ns=3500,
                                        duration_ns=500)])
    t = trace.from_planes([NS(name="/host:CPU", lines=[host]),
                           NS(name="/device:TPU:0", lines=[dev])])
    reader = spec.metric_reader(metric)
    assert reader.SPANS == names
    want = 100.0 * spans.idle_under(t, names) / t.window_s
    assert reader.read(NS(trace=t)) == pytest.approx(want)
    assert want == pytest.approx(100.0 * {"engine.decode": 1500,
                                          "engine.scatter": 1000}[names[0]]
                                 / 9000)


def test_the_cells_entries_in_the_benchmark():
    """The cell reads the latency cell's family-neutral metrics under the
    same names as ``gru-jet.latency``, so the two cells compare metric for
    metric. Its own names: the sLSTM kernels' rooflines, and the decode
    span's idle share, whose shared entry is held to ``gru-jet.latency``."""
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    ours = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    own = {"slstm_seq_kernel_roofline.latency",
           "slstm_decode_kernel_roofline.latency",
           "slstm_decode_idle_share.latency"}
    shared = {"prefill_share.latency", "idle_share.latency", "mfu.latency",
              "scatter_idle_share.latency"}
    assert set(ours) == own | shared
    for name, m in ours.items():
        want = [CELL] if name in own else ["gru-jet.latency", CELL]
        assert m["workloads"] == want and m["moves"] == "event_p99_ms"
    assert set(LIMITS) == set(check.NUMBERS)
