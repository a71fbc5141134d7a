"""ServeEngine's host spans: one ``engine.step`` per ``gru_wave_step``, its
phases nested inside it in order, and nothing served differently with
them (CPU, under ``jax.profiler``, as a benchmark's traced run takes it)."""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.params import init_params
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi
from repro.serve import engine as engine_mod
from repro.serve.engine import (DECODE_SPANS, SPAN_DECODE, SPAN_PREFILL,
                                SPAN_READOUT, SPAN_STEP, STEP_SPANS, Request,
                                ServeEngine)

ADMIT_FREE = DECODE_SPANS           # feed, decode, readout, retire
BUDGETS = [2, 5, 3, 4, 1, 2, 3]
RECORDS = ("step_times", "prefill_times", "queue_waits", "e2e_times",
           "decode_backends", "prefill_backends")


def _requests():
    rng = np.random.default_rng(7)
    return [Request(prompt=rng.normal(size=(3 + i % 4, 5)).astype(np.float32),
                    max_new_tokens=n,
                    stream=(rng.normal(size=(n, 5)).astype(np.float32)
                            if i % 2 else None))
            for i, n in enumerate(BUDGETS)]


def _serve(eng):
    """Serve one wave step by step; whether each step admitted."""
    reqs = _requests()
    eng.gru_wave_enqueue(reqs)
    admitted = []
    while eng.gru_wave_active():
        n = len(eng.queue_waits)
        eng.gru_wave_step()
        admitted.append(len(eng.queue_waits) > n)
    return reqs, admitted


def _engine_spans(log_dir):
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("engine.")]
            if evs:
                return sorted(evs, key=lambda h: (h[1], -h[2]))
    return []


def _run(mode, log_dir):
    """A warmed engine's wave: ``no_spans`` with the spans replaced by
    empty contexts, ``off`` with the spans and no profiler, ``traced``
    under the profiler."""
    cfg = get_smoke_config("gru-jet")
    params = init_params(mapi.get_api(cfg).specs(cfg), jax.random.key(0),
                         cfg.param_dtype)
    with pytest.MonkeyPatch.context() as mp:
        if mode == "no_spans":
            mp.setattr(engine_mod, "TraceAnnotation",
                       lambda name: contextlib.nullcontext())
        eng = ServeEngine(cfg, params, ShardCtx(), max_batch=3,
                          bucket_min=8)
        _serve(eng)                     # every program compiles here
        marks = {a: len(getattr(eng, a)) for a in RECORDS}
        if mode == "traced":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            reqs, admitted = _serve(eng)
        finally:
            if mode == "traced":
                jax.profiler.stop_trace()
    records = {a: getattr(eng, a)[marks[a]:] for a in RECORDS}
    return {"reqs": reqs, "admitted": admitted, "records": records,
            "state": np.asarray(eng._wave.cache["h"][0]),
            "spans": _engine_spans(log_dir) if mode == "traced" else []}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    got = {}

    def get(mode):
        if mode not in got:
            got[mode] = _run(mode, str(tmp_path_factory.mktemp(mode)))
        return got[mode]
    return get


def _steps(spans):
    """Each ``engine.step`` span with the engine spans inside it."""
    steps = [s for s in spans if s[0] == SPAN_STEP]
    return [(s, [c for c in spans if c[0] != SPAN_STEP
                 and s[1] <= c[1] and c[2] <= s[2]]) for s in steps]


def test_one_step_span_per_step_with_its_phases_in_order(runs):
    r = runs("traced")
    steps = _steps(r["spans"])
    assert len(steps) == len(r["admitted"]) > 0
    assert any(r["admitted"]) and not all(r["admitted"])
    # every phase span lies in one step
    assert sum(len(c) for _, c in steps) == len(r["spans"]) - len(steps)
    for (_, children), admitted in zip(steps, r["admitted"]):
        names = [c[0] for c in children]
        assert names == list(STEP_SPANS if admitted else ADMIT_FREE)
        # siblings, one after another
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))


def test_phases_cover_the_step(runs):
    steps = _steps(runs("traced")["spans"])
    total = sum(s[2] - s[1] for s, _ in steps)
    covered = sum(c[2] - c[1] for _, children in steps for c in children)
    assert covered >= 0.9 * total, (covered, total)


@pytest.mark.parametrize("span,record", [("engine.decode", "step_times"),
                                         ("engine.prefill", "prefill_times")])
def test_call_spans_agree_with_the_engine_records(runs, span, record):
    """A ``prefill_times`` record is its admitting step's
    ``engine.prefill`` span, the dispatch of the step's one program. A
    ``step_times`` record runs from the step's dispatch to the classes in
    hand, so it is the step's ``engine.decode`` (``engine.prefill`` on an
    admitting step) and ``engine.readout``."""
    r = runs("traced")
    parts = ((SPAN_DECODE, SPAN_PREFILL, SPAN_READOUT)
             if span == SPAN_DECODE else (span,))
    got = [sum(e - s for n, s, e in children if n in parts) * 1e-9
           for _, children in _steps(r["spans"])
           if any(n in parts for n, _, _ in children)]
    want = r["records"][record]
    calls = (len(r["admitted"]) if span == "engine.decode"
             else sum(r["admitted"]))
    assert len(got) == len(want) == calls
    for g, w in zip(got, want):
        assert abs(g - w) <= max(0.1 * w, 50e-6), (g, w)


@pytest.mark.parametrize("mode", ["off", "traced"])
def test_spans_change_nothing_served(runs, mode):
    base, r = runs("no_spans"), runs(mode)
    assert [q.out for q in r["reqs"]] == [q.out for q in base["reqs"]]
    assert [len(q.out) for q in r["reqs"]] == BUDGETS
    assert r["admitted"] == base["admitted"]
    assert np.array_equal(r["state"], base["state"])
    for a in RECORDS:
        if a.endswith("backends"):
            assert r["records"][a] == base["records"][a]
        else:
            assert len(r["records"][a]) == len(base["records"][a])
