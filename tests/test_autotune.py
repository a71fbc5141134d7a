"""The online autotuner: measured timings -> engine configuration.

Covers the feedback loop's three dimensions (wave size from the measured
batch-latency curve, quantile bucket ladder from observed prompt lengths,
online CostModel recalibration with epoch bumps), the wave-boundary-only
retune invariant (zero mid-wave retraces, jit-count asserted), the
post-retune compile-step exclusion in latency_stats, and the
recalibration safety properties (legal candidate set, pin immunity,
old-epoch cache eviction — property-fuzzed with hypothesis).

Everything runs under deterministic clocks: a plain ManualClock measures
dt == 0 (which the tuner must IGNORE), and an auto-advancing subclass
produces nonzero deterministic timings for the recalibration paths. No
sleeps anywhere.
"""
import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.configs.base import GRUConfig, get_smoke_config
from repro.core import runtime
from repro.core.params import init_params
from repro.distributed.fault_tolerance import ManualClock
from repro.distributed.sharding import ShardCtx
from repro.models import api as mapi
from repro.serve.autotune import AutoTuneConfig, AutoTuner
from repro.serve.engine import Request, ServeEngine, bucket_len


def _setup(hidden=12, num_layers=1, backend="xla"):
    cfg = get_smoke_config("gru-jet").replace(
        gru=GRUConfig(input_dim=5, hidden_dim=hidden, num_classes=5,
                      seq_len=20, num_layers=num_layers, backend=backend))
    A = mapi.get_api(cfg)
    params = init_params(A.specs(cfg), jax.random.key(0), cfg.param_dtype)
    return cfg, params


def _requests(cfg, lens, seed=0, max_new=4):
    rng = np.random.default_rng(seed)
    X = cfg.gru.input_dim
    return [Request(prompt=rng.normal(size=(int(L), X)).astype(np.float32),
                    max_new_tokens=max_new) for L in lens]


class _AutoClock(ManualClock):
    """ManualClock that advances a fixed dt per now() call: step timings
    measured as now() deltas come out nonzero AND deterministic."""

    def __init__(self, dt_s: float = 1e-4):
        super().__init__()
        self._dt_s = dt_s

    def now(self) -> float:
        t = super().now()
        self.advance(self._dt_s)
        return t


def _install_curve(backend, points, *, depth=1, hidden=12, op="decode"):
    """Install a synthetic measured batch-latency curve for one backend
    (callers restore the prior model via try/finally)."""
    entries = [{"family": "gru", "backend": backend, "op": op,
                "depth": depth, "hidden_dim": hidden, "batch": b,
                "p50_us": us} for b, us in points]
    runtime.set_cost_model(runtime.CostModel.from_entries(
        entries, source="<test curve>"))


# ---------------------------------------------------------------------------
# CostModel.merged / batch_points (the runtime half of the loop)
# ---------------------------------------------------------------------------

def test_cost_model_merged_replaces_and_extends():
    base = runtime.CostModel.from_entries([
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 1, "p50_us": 100.0},
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 8, "p50_us": 200.0}])
    out = base.merged([
        # replaces the batch=1 point
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 1, "p50_us": 50.0},
        # extends the curve at a new batch
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 4, "p50_us": 120.0}])
    assert out.batch_points("xla", "decode", depth=1, hidden=12) == \
        [(1, 50.0), (4, 120.0), (8, 200.0)]
    # pure: the base model is untouched
    assert base.lookup("xla", "decode", depth=1, batch=1, hidden=12) == 100.0
    assert base.batch_points("xla", "decode", depth=1, hidden=12) == \
        [(1, 100.0), (8, 200.0)]


def test_cost_model_merged_skips_malformed_rows():
    base = runtime.CostModel.from_entries([
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 2, "p50_us": 10.0}])
    out = base.merged([
        {"backend": "xla"},                                   # missing keys
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 0, "p50_us": 5.0},                          # batch < 1
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 2, "p50_us": 0.0},                          # ManualClock dt
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 2, "p50_us": float("nan")},
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 2, "p50_us": float("inf")},
        {"backend": "xla", "op": "decode", "depth": 1, "hidden_dim": 12,
         "batch": 2, "p50_us": -3.0}])
    # every row was bad: the measured point survives unchanged
    assert out.batch_points("xla", "decode", depth=1, hidden=12) == \
        [(2, 10.0)]


# ---------------------------------------------------------------------------
# dimension 1: wave size from the measured batch-latency curve
# ---------------------------------------------------------------------------

def test_wave_size_follows_marginal_cost_rule():
    cfg, params = _setup()
    snap = runtime.cost_model()
    try:
        # step(1)=10us; adding slots is ~free until B=3, then the curve
        # kinks: marginal cap = 0.5 x 10 = 5us, step(4)-step(3) = 18 > 5
        _install_curve("xla", [(1, 10.0), (2, 11.0), (3, 12.0),
                               (4, 30.0), (8, 100.0)])
        tuner = AutoTuner(AutoTuneConfig(tune_buckets=False,
                                         recalibrate=False,
                                         marginal_frac=0.5, wave_cap=8))
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=8,
                             clock=ManualClock(), tuner=tuner)
        engine.gru_wave_begin(())        # a wave boundary: retune runs
        assert engine.max_batch == 3
        (d,) = tuner.decisions
        assert d["kind"] == "wave_size" and d["from"] == 8 and d["to"] == 3
        m = d["measurement"]
        assert m["backend"] == "xla" and m["solo_us"] == 10.0
        assert [1, 10.0] in m["curve_us"]
        # idempotent: the same curve produces no second decision
        engine.gru_wave_begin(())
        assert len(tuner.decisions) == 1
    finally:
        runtime.set_cost_model(snap)


def test_wave_size_needs_a_measured_curve():
    """With < 2 measured batch points there is no curve: the operator's
    static wave size stands and no decision is recorded."""
    cfg, params = _setup()
    snap = runtime.cost_model()
    try:
        _install_curve("xla", [(1, 10.0)])
        tuner = AutoTuner(AutoTuneConfig(tune_buckets=False,
                                         recalibrate=False))
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=4,
                             clock=ManualClock(), tuner=tuner)
        engine.gru_wave_begin(())
        assert engine.max_batch == 4 and tuner.decisions == []
    finally:
        runtime.set_cost_model(snap)


def test_wave_size_respects_step_budget():
    cfg, params = _setup()
    snap = runtime.cost_model()
    try:
        # smooth marginals everywhere, but an absolute per-step deadline
        # of 12us caps the wave at the largest batch under budget
        _install_curve("xla", [(1, 10.0), (2, 11.0), (3, 12.0), (4, 13.0),
                               (8, 17.0)])
        tuner = AutoTuner(AutoTuneConfig(tune_buckets=False,
                                         recalibrate=False, wave_cap=8,
                                         marginal_frac=1.0,
                                         step_budget_us=12.0))
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=8,
                             clock=ManualClock(), tuner=tuner)
        engine.gru_wave_begin(())
        assert engine.max_batch == 3
    finally:
        runtime.set_cost_model(snap)


# ---------------------------------------------------------------------------
# dimension 2: bucket ladder from the observed prompt-length distribution
# ---------------------------------------------------------------------------

def test_bucket_ladder_from_skewed_prompt_distribution():
    cfg, params = _setup()
    tuner = AutoTuner(AutoTuneConfig(tune_wave_size=False,
                                     recalibrate=False,
                                     ladder_min_prompts=8))
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2,
                         clock=ManualClock(), tuner=tuner)
    # heavily skewed: most prompts are tiny, a few are long — the static
    # pow2 ladder would pad everything short up to 8
    for L in [3] * 51 + [5] * 30 + [9] * 15 + [16] * 5:
        tuner.observe_prompt(L)
    engine.gru_wave_begin(())
    assert engine.bucket_ladder == (3, 5, 9, 16)
    (d,) = tuner.decisions
    assert d["kind"] == "bucket_ladder" and d["to"] == [3, 5, 9, 16]
    assert d["measurement"]["prompts"] == 101
    # the tuned ladder really differs from the static pow2 buckets
    assert engine._bucket_for(3) == 3 != bucket_len(3, engine.bucket_min)
    assert engine._bucket_for(4) == 5
    assert engine._bucket_for(16) == 16
    # beyond the top rung: doubles from it (a bounded jit-key space)
    assert engine._bucket_for(17) == 32
    # too few observations -> no decision
    t2 = AutoTuner(AutoTuneConfig(ladder_min_prompts=8))
    e2 = ServeEngine(cfg, params, ShardCtx(), clock=ManualClock(), tuner=t2)
    for L in (3, 4, 5):
        t2.observe_prompt(L)
    e2.gru_wave_begin(())
    assert e2.bucket_ladder is None and t2.decisions == []


# ---------------------------------------------------------------------------
# dimension 3: online recalibration (epoch bump, no needless retrace)
# ---------------------------------------------------------------------------

def test_recalibration_folds_steps_and_bumps_epoch_without_retrace():
    """Served warm-step timings become fresh CostModel rows (epoch bump);
    when the refreshed table does NOT change the resolved backend, the
    live jits survive untouched (zero retraces)."""
    cfg, params = _setup()                   # backend="xla": pinned family
    snap = runtime.cost_model()
    try:
        tuner = AutoTuner(AutoTuneConfig(tune_wave_size=False,
                                         tune_buckets=False,
                                         recal_min_steps=4))
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2,
                             clock=_AutoClock(1e-4), tuner=tuner)
        engine.generate(_requests(cfg, [3, 3], max_new=6))
        epoch0 = runtime.cost_epoch()
        gen0 = engine._jit_gen
        decode_jits0 = dict(engine._decode_jit)
        # the drain boundary inside generate() already ran maybe_retune;
        # warm steps (>= 4 of them at 2 slots x 6 tokens) were folded
        recs = [d for d in tuner.decisions if d["kind"] == "recalibrate"]
        if not recs:                         # fold on the next boundary
            engine.generate(_requests(cfg, [3, 3], max_new=6))
            recs = [d for d in tuner.decisions
                    if d["kind"] == "recalibrate"]
        assert recs, tuner.decisions
        d = recs[0]
        assert d["to"] > d["from"]           # the epoch really bumped
        assert d["rebuilt_jits"] is False    # same resolution: no retrace
        assert engine._jit_gen == gen0
        for k, v in decode_jits0.items():    # the SAME jit objects live on
            assert engine._decode_jit.get(k) is v
        assert runtime.cost_epoch() > epoch0 or d["to"] <= epoch0
        # the folded rows are real measured rows at the served shape
        entries = d["measurement"]["entries"]
        assert entries and all(e["backend"] == "xla" and e["p50_us"] > 0
                               for e in entries)
        assert runtime.cost_model().batch_points(
            "xla", "decode", depth=1, hidden=12)
    finally:
        runtime.set_cost_model(snap)


def test_recalibration_ignores_manualclock_zero_timings():
    """Under a plain ManualClock every measured dt is 0.0 — the tuner
    must never fold 'free' rows into the table."""
    cfg, params = _setup()
    snap = runtime.cost_model()
    try:
        tuner = AutoTuner(AutoTuneConfig(tune_wave_size=False,
                                         tune_buckets=False,
                                         recal_min_steps=1))
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2,
                             clock=ManualClock(), tuner=tuner)
        engine.generate(_requests(cfg, [3, 3], max_new=6))
        engine.generate(_requests(cfg, [3, 3], max_new=6))
        assert [d for d in tuner.decisions
                if d["kind"] == "recalibrate"] == []
        assert runtime.cost_model() is snap  # never touched
    finally:
        runtime.set_cost_model(snap)


# ---------------------------------------------------------------------------
# satellite: post-retune compile-step exclusion in latency_stats
# ---------------------------------------------------------------------------

def test_post_retune_prefill_jit_first_call_excluded():
    """A bucket jit created AFTER a retune compiles mid-serve; its first
    call is excluded from prefill percentiles — while first-EVER bucket
    compiles (before any retune) stay included, and the second use of a
    post-retune bucket records normally."""
    cfg, params = _setup()
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2,
                         clock=ManualClock())
    engine.generate(_requests(cfg, [3, 3], max_new=2))
    assert len(engine.prefill_times) == 1    # gen-0 compile: included
    # a ladder retune between waves: prompts of length 3 now land in a
    # NEW bucket (3), whose jit does not exist yet
    engine.apply_bucket_ladder((3, 16))
    engine.generate(_requests(cfg, [3, 3], max_new=2))
    assert len(engine.prefill_times) == 1    # post-retune compile: excluded
    engine.generate(_requests(cfg, [3, 3], max_new=2))
    assert len(engine.prefill_times) == 2    # warm reuse: recorded


def test_post_retune_decode_jit_first_step_excluded_again():
    """After an invalidating retune (e.g. a recalibration that changed a
    resolved backend), the re-created decode jit's first step is a
    compile again and must be excluded — same per-jit rule as its first
    life, even though the key is unchanged."""
    cfg, params = _setup()
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2,
                         clock=ManualClock())
    engine.generate(_requests(cfg, [3, 3], max_new=3))
    n0 = len(engine.step_times)
    assert n0 == 3 - 1                       # first step excluded per key
    engine._invalidate_jits()                # what a backend-change does
    assert engine._decode_jit == {} and engine._decode_warm == set()
    engine.generate(_requests(cfg, [3, 3], max_new=3))
    # the re-created jit recorded one step fewer than it ran
    assert len(engine.step_times) == n0 + 3 - 1
    # prefill side of the same invalidation: bucket 8's jit was dropped
    # too, so its post-retune re-compile is excluded...
    assert len(engine.prefill_times) == 1
    engine.generate(_requests(cfg, [3, 3], max_new=3))
    # ...while its warm reuse records normally again
    assert len(engine.prefill_times) == 2


# ---------------------------------------------------------------------------
# acceptance: the full loop on a skewed workload, boundary-only retuning
# ---------------------------------------------------------------------------

def test_autotuned_engine_acceptance_skewed_workload():
    """End-to-end under deterministic virtual time: an autotuned engine
    on a skewed prompt-length workload ends with a bucket ladder AND wave
    size that differ from the static defaults; every decision carries its
    justifying measurement; streams are bitwise-identical to an untuned
    engine; and no retune ever fires mid-wave (asserted on every mutate)
    nor does any jit silently retrace (jax cache size == 1 per jit)."""
    cfg, params = _setup()
    lens = [3, 3, 3, 5, 3, 3, 5, 9, 3, 5, 3, 16, 3, 5, 3, 3]
    snap = runtime.cost_model()
    try:
        _install_curve("xla", [(1, 10.0), (2, 11.0), (4, 40.0), (8, 90.0)])
        # recalibration off: the auto-advancing clock's synthetic step
        # timings would overwrite the installed curve mid-test and make
        # the expected wave size depend on fold timing; the recal
        # dimension has its own end-to-end tests above
        tuner = AutoTuner(AutoTuneConfig(ladder_min_prompts=8,
                                         recalibrate=False,
                                         marginal_frac=0.5, wave_cap=8))
        engine = ServeEngine(cfg, params, ShardCtx(), max_batch=4,
                             clock=_AutoClock(1e-4), tuner=tuner)

        # spy: every tuner-driven mutation must happen at a wave boundary
        boundary_violations = []
        real_retune = tuner.maybe_retune

        def guarded(eng):
            if eng._wave is not None and eng.gru_wave_active() > 0:
                boundary_violations.append(eng.gru_wave_active())
            return real_retune(eng)

        tuner.maybe_retune = guarded
        outs_tuned = []
        for i in range(0, len(lens), 4):
            reqs = _requests(cfg, lens[i:i + 4], seed=i, max_new=4)
            engine.generate(reqs)
            outs_tuned.extend(r.out for r in reqs)

        assert boundary_violations == []
        # tuned shape differs from the static defaults on BOTH dimensions
        assert engine.max_batch == 2 != 4          # curve kinks after B=2
        assert engine.bucket_ladder is not None
        assert set(engine.bucket_ladder) != {
            bucket_len(L, 8) for L in lens}        # not the pow2 ladder
        stats = engine.latency_stats()
        at = stats["autotune"]
        assert at["enabled"] and at["wave_size"] == 2
        assert at["bucket_ladder"] == list(engine.bucket_ladder)
        kinds = {d["kind"] for d in at["decisions"]}
        assert {"wave_size", "bucket_ladder"} <= kinds
        for d in at["decisions"]:                  # measurement-justified
            assert d["measurement"] and "rule" in d["measurement"]
            assert "from" in d and "to" in d and d["t"] >= 0.0
        # no silent retraces: every live jit traced exactly one shape
        for jit_fn in (list(engine._decode_jit.values())
                       + list(engine._prefill_jit.values())):
            cache_size = getattr(jit_fn, "_cache_size", None)
            if cache_size is not None:
                assert cache_size() == 1
        # stream parity vs an untuned engine on the identical workload
        untuned = ServeEngine(cfg, params, ShardCtx(), max_batch=4,
                              clock=_AutoClock(1e-4))
        outs_ref = []
        for i in range(0, len(lens), 4):
            reqs = _requests(cfg, lens[i:i + 4], seed=i, max_new=4)
            untuned.generate(reqs)
            outs_ref.extend(r.out for r in reqs)
        assert outs_tuned == outs_ref
    finally:
        runtime.set_cost_model(snap)


def test_untuned_engine_reports_autotune_disabled():
    cfg, params = _setup()
    engine = ServeEngine(cfg, params, ShardCtx(), max_batch=2)
    engine.generate(_requests(cfg, [3], max_new=2))
    at = engine.latency_stats()["autotune"]
    assert at == {"enabled": False, "wave_size": 2, "bucket_ladder": None}


# ---------------------------------------------------------------------------
# satellite: recalibration safety properties (hypothesis)
# ---------------------------------------------------------------------------

_BACKENDS = ["xla", "pallas_fused", "pallas_chain", "bogus_backend",
             "sharded_decode", "pallas_fused_q8"]


def _legal_decode_set(cfg):
    """The legal candidate set for a host decode call of this config —
    computed from the registry the same way compile() filters."""
    from repro.core.runtime import _REGISTRY, _legal
    return {name for (fam, name), s in _REGISTRY.items()
            if fam == "gru" and _legal(s, op="decode", masked=False,
                                       hetero=False, mesh=None, cfg=cfg)}


@settings(max_examples=25, deadline=None, derandomize=True)
@given(entries=st.lists(st.fixed_dictionaries({
    "backend": st.sampled_from(_BACKENDS),
    "op": st.sampled_from(["decode", "sequence"]),
    "depth": st.integers(min_value=1, max_value=2),
    "hidden_dim": st.sampled_from([12, 32]),
    "batch": st.integers(min_value=-2, max_value=16),
    # hypothesis refuses bounds together with nan/inf: draw those apart
    "p50_us": st.one_of(st.floats(min_value=-1e6, max_value=1e6, width=32),
                        st.sampled_from([float("nan"), float("inf"),
                                         float("-inf")])),
}), max_size=12))
def test_prop_recalibration_never_escapes_legal_set(entries):
    """Folding ARBITRARY served-timing entries into the CostModel — junk
    backends, absurd batches, nan/inf/negative latencies — (1) never
    makes auto-dispatch select outside the legal candidate set, (2) never
    overrides an exact backend-name pin, (3) leaves older epochs
    unreachable in the executable cache."""
    snap = runtime.cost_model()
    auto_cfg = GRUConfig(input_dim=5, hidden_dim=12, num_layers=1,
                         backend="auto")
    pin_cfg = GRUConfig(input_dim=5, hidden_dim=12, num_layers=1,
                        backend="pallas_chain")
    try:
        merged = runtime.cost_model().merged(entries, source="<prop>")
        runtime.set_cost_model(merged)
        assert runtime._EXEC_CACHE == {}     # the bump evicted everything
        epoch = runtime.cost_epoch()
        exe = runtime.compile(auto_cfg, batch=2, mode="decode")
        assert exe.decode_backend in _legal_decode_set(auto_cfg)
        assert exe.decode_backend != "bogus_backend"
        # quant gate closed (conftest): q8 must not be selectable by cost
        assert not exe.decode_backend.endswith("_q8")
        # exact-name pins bypass cost selection entirely
        pin = runtime.compile(pin_cfg, batch=2, mode="decode")
        assert pin.decode_backend == "pallas_chain"
        # every cached executable belongs to the CURRENT epoch
        assert runtime._EXEC_CACHE
        assert all(k[-1] == epoch for k in runtime._EXEC_CACHE)
    finally:
        runtime.set_cost_model(snap)


def test_recalibration_epoch_evicts_stale_executables():
    """The non-fuzzed core of the property: an executable compiled under
    epoch N is unreachable after a fold installs epoch N+1 — compile()
    returns a FRESH object keyed to the new epoch."""
    snap = runtime.cost_model()
    cfg = GRUConfig(input_dim=5, hidden_dim=12, num_layers=1,
                    backend="auto")
    try:
        exe_old = runtime.compile(cfg, batch=1, mode="decode")
        runtime.set_cost_model(runtime.cost_model().merged(
            [{"backend": "xla", "op": "decode", "depth": 1,
              "hidden_dim": 12, "batch": 1, "p50_us": 7.0}]))
        assert exe_old not in runtime._EXEC_CACHE.values()
        exe_new = runtime.compile(cfg, batch=1, mode="decode")
        assert exe_new is not exe_old
        assert runtime.compile(cfg, batch=1, mode="decode") is exe_new
    finally:
        runtime.set_cost_model(snap)
